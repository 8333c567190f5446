"""ctl CLI: list/add/remove model registrations against a live fabric."""

import asyncio
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")


def _ctl(fabric, *args):
    return subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.cli.run", "ctl",
         "--fabric", fabric, *args],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=60,
    )


def test_ctl_add_list_remove():
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.fabric import FabricServer

    async def main():
        server = FabricServer(port=0)
        await server.start()
        addr = server.address
        try:
            # register one live instance so `list` shows both sections
            rt = await DistributedRuntime.create(addr)
            ep = rt.namespace("dynamo").component("backend").endpoint("generate")
            reg = await ep.register("127.0.0.1", 7001, metadata={})

            out = await run_in_executor(_ctl, addr, "add", "my-model",
                                        "--router-mode", "kv")
            assert "registered my-model" in out.stdout, out.stderr

            out = await run_in_executor(_ctl, addr, "list")
            assert "my-model" in out.stdout
            assert "router=kv" in out.stdout
            assert reg.instance.instance_id in out.stdout

            out = await run_in_executor(_ctl, addr, "remove", "my-model")
            assert "removed 1 registration(s)" in out.stdout

            out = await run_in_executor(_ctl, addr, "list")
            assert "my-model" not in out.stdout

            await reg.deregister()
            await rt.close()
        finally:
            await server.stop()

    async def run_in_executor(fn, *args):
        return await asyncio.get_running_loop().run_in_executor(
            None, lambda: fn(*args)
        )

    asyncio.run(main())


def test_run_cli_decode_steps_flag_reaches_engine_config():
    """--decode-steps plumbs through to EngineConfig (the decode-fusion
    knob the benchmarks pass explicitly)."""
    import argparse

    from dynamo_tpu.cli.run import _engine_config, build_parser

    p = build_parser()
    args = p.parse_args(
        ["run", "in=text", "out=jax", "--model", "tiny",
         "--decode-steps", "64"]
    )
    assert _engine_config(args).decode_steps == 64
    # default: engine default (8)
    args = p.parse_args(["run", "in=text", "out=jax", "--model", "tiny"])
    assert _engine_config(args).decode_steps == 8
