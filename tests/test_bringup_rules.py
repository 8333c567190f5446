"""The bring-up rules of ISSUE 21: where the compile cache lives, what a
TPU the peaks table does not know does, what keys the native library,
which roles stay off jax, and what chip_smoke.py says without a chip."""

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

import pytest

from benchmarks._procs import free_port as _free_port

REPO = Path(__file__).resolve().parent.parent
ENV = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")


# -- compile cache ------------------------------------------------------------


def test_compile_cache_dir_follows_the_environment_variable(tmp_path):
    env = dict(ENV, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "placed"))
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from dynamo_tpu.platform import enable_persistent_compile_cache\n"
         "print(enable_persistent_compile_cache())\n"
         "print(jax.config.jax_persistent_cache_min_compile_time_secs)"],
        env=env, capture_output=True, text=True, timeout=120, cwd=tmp_path,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    cache_dir, min_secs = out.stdout.split()
    assert cache_dir == str(tmp_path / "placed")
    assert float(min_secs) == 0.2
    # jax reads the variable itself: nothing was created in code
    assert not (tmp_path / "placed").exists()


def test_compile_cache_dir_is_fixed_in_the_checkout_otherwise(
    monkeypatch, tmp_path
):
    import jax

    from dynamo_tpu import platform

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        pytest.skip("this run's environment places the cache itself")
    monkeypatch.chdir(tmp_path)  # whatever the cwd
    cache_dir = platform.enable_persistent_compile_cache()
    # one fixed place inside the checkout: never $HOME, a temp dir, a pid
    # or a time
    assert cache_dir == str(REPO / ".jax_cache")
    assert cache_dir == platform.DEFAULT_COMPILE_CACHE_DIR
    assert not cache_dir.startswith(
        (os.path.expanduser("~/.cache"), tempfile.gettempdir())
    )
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.2


# -- device capacity -----------------------------------------------------------


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e", "TPU v5litepod"])
def test_device_peaks_resolves_v5e_kind_strings(monkeypatch, kind):
    import jax

    from dynamo_tpu import platform

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(kind)])
    assert platform.device_hbm_bytes() == 16e9


def test_device_peaks_raises_for_a_tpu_kind_the_table_does_not_know(
    monkeypatch,
):
    import jax

    from dynamo_tpu import platform

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v9 mega")])
    monkeypatch.setenv("DYNTPU_HBM_BYTES", "5e9")  # no way around it
    with pytest.raises(ValueError, match="TPU v9 mega"):
        platform.device_hbm_bytes()


def test_require_platform_refuses_a_cpu_it_was_not_asked_for(monkeypatch):
    """JAX_PLATFORMS unset + no TPU: jax falls back to the CPU on its
    own; an engine, bench.py and __graft_entry__.entry() must not."""
    import jax

    from dynamo_tpu import platform

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert platform.require_platform() == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    # this process's backend IS the cpu: exactly what the fallback gives
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="one process at a time"):
        platform.require_platform()


# -- native library keyed on its sources --------------------------------------


def test_native_library_is_rebuilt_when_content_changes_but_mtime_does_not(
    monkeypatch, tmp_path
):
    from dynamo_tpu import native

    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no toolchain")
    src = tmp_path / "native"
    src.mkdir()
    (src / "xxh3.h").write_text("// header\n")
    (src / "one.cpp").write_text('extern "C" int answer() { return 1; }\n')
    (src / "Makefile").write_text(
        "LIB := build/lib.so\n"
        "$(LIB): one.cpp xxh3.h\n"
        "\t@mkdir -p build\n"
        "\tg++ -shared -fPIC one.cpp -o $(LIB)\n"
    )
    monkeypatch.setattr(native, "_NATIVE_DIR", src)

    first = native.lib_path()
    assert not first.exists()
    assert native._build() and first.exists()

    # same size, same mtime, other content: file times cannot see it
    stat = (src / "one.cpp").stat()
    (src / "one.cpp").write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src / "one.cpp", ns=(stat.st_atime_ns, stat.st_mtime_ns))
    assert (src / "one.cpp").stat().st_mtime_ns == stat.st_mtime_ns

    second = native.lib_path()
    assert second != first and not second.exists()
    assert native._build() and second.exists()
    # the library of the other sources is gone, not left to be loaded
    assert not first.exists()
    import ctypes

    assert ctypes.CDLL(str(second)).answer() == 2


def test_native_library_path_is_keyed_on_every_tracked_source(
    monkeypatch, tmp_path
):
    from dynamo_tpu import native

    src = tmp_path / "native"
    shutil.copytree(REPO / "native", src, ignore=shutil.ignore_patterns("build"))
    monkeypatch.setattr(native, "_NATIVE_DIR", src)
    seen = {native.lib_path().name}
    assert native.lib_path().name in seen  # stable for unchanged sources
    for name in ("pool.cpp", "xxh3.h", "Makefile"):
        with open(src / name, "a") as f:
            f.write("\n# touched\n" if name == "Makefile" else "\n// touched\n")
        seen.add(native.lib_path().name)
    assert len(seen) == 4


# -- roles that need no chip never initialise a backend ------------------------

_PROBE = """
import atexit, sys
def _report():
    xb = sys.modules.get("jax._src.xla_bridge")
    n = len(xb._backends) if xb is not None else 0
    print(f"JAX_BACKENDS_INITIALISED={n}", flush=True)
atexit.register(_report)
sys.argv = ["dynamo-tpu", *sys.argv[1:]]
from dynamo_tpu.cli.run import main
main()
"""


class _Role:
    def __init__(self, *argv):
        self.log = tempfile.NamedTemporaryFile(
            mode="w+", suffix=".log", delete=False
        )
        self.proc = subprocess.Popen(
            [sys.executable, "-c", _PROBE, *argv], env=ENV, cwd=REPO,
            stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def text(self) -> str:
        with open(self.log.name) as f:
            return f.read()

    def wait_for(self, needle: str, timeout: float = 40.0) -> None:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if needle in self.text():
                return
            assert self.proc.poll() is None, self.text()[-3000:]
            time.sleep(0.1)
        raise AssertionError(f"{needle!r} not seen:\n{self.text()[-3000:]}")

    def finish(self) -> str:
        """SIGINT (so atexit runs), then the whole log."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        out = self.text()
        self.log.close()
        os.unlink(self.log.name)
        return out


@pytest.fixture(scope="module")
def chipless_fleet():
    """fabric + an echo worker + every role that needs no chip, with one
    chat request served through the frontend; yields role -> final log."""
    fport, hport, mport = _free_port(), _free_port(), _free_port()
    fabric_at = f"127.0.0.1:{fport}"
    roles: dict[str, _Role] = {}
    logs: dict[str, str] = {}
    try:
        roles["fabric"] = _Role("fabric", "--port", str(fport))
        roles["fabric"].wait_for("fabric server on")
        roles["echo_worker"] = _Role(
            "run", "in=dyn", "out=echo", "--model", "tiny",
            "--fabric", fabric_at,
        )
        roles["http_dyn"] = _Role(
            "run", "in=http", "out=dyn", "--fabric", fabric_at,
            "--port", str(hport),
        )
        roles["metrics"] = _Role(
            "metrics", "--fabric", fabric_at, "--port", str(mport)
        )
        roles["planner"] = _Role(
            "planner", "--fabric", fabric_at, "--interval", "1"
        )
        roles["router"] = _Role(
            "router", "--fabric", fabric_at, "--salt", "tiny",
            "--block-size", "4",
        )
        roles["echo_worker"].wait_for(" up")
        roles["http_dyn"].wait_for("listening on")
        body = json.dumps({
            "model": "tiny", "max_tokens": 4,
            "messages": [{"role": "user", "content": "hi"}],
        }).encode()
        deadline = time.time() + 30
        while True:  # the frontend attaches the model a moment after boot
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{hport}/v1/chat/completions",
                    data=body, headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=10) as resp:
                    assert resp.status == 200
                break
            except OSError:
                assert time.time() < deadline, roles["http_dyn"].text()[-3000:]
                time.sleep(0.3)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{mport}/metrics", timeout=10
        ) as resp:
            assert resp.status == 200
        time.sleep(1.1)  # one planner tick
    finally:
        for name in reversed(list(roles)):
            logs[name] = roles[name].finish()
    yield logs


@pytest.mark.parametrize(
    "role", ["fabric", "http_dyn", "metrics", "planner", "router"]
)
def test_chipless_role_never_initialises_a_jax_backend(chipless_fleet, role):
    """A frontend that creates one jnp array takes the chip from the
    worker beside it: these roles must leave jax's backends alone."""
    assert "JAX_BACKENDS_INITIALISED=0" in chipless_fleet[role], (
        chipless_fleet[role][-3000:]
    )


# -- chip_smoke.py without a chip ---------------------------------------------


def _chip_smoke(*argv, env=ENV, cwd=REPO, timeout=600):
    return subprocess.run(
        [sys.executable, str(Path(cwd) / "chip_smoke.py"), *argv],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=timeout,
    )


def test_chip_smoke_without_a_chip_fails_and_says_cpu():
    out = _chip_smoke()
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert set(last) == {"ok", "device"}
    assert '"ok": true' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    out = _chip_smoke(env=env, cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal_walks_every_phase_on_cpu(chips):
    """`--rehearse`: the same phases at the tiny preset, interpreted
    kernels — every check passes, and it still is not a pass."""
    env = dict(
        ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4"
    )
    out = _chip_smoke("--rehearse", "--chips", str(chips), env=env)
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert lines[-2]["phase"] == "summary", out.stderr[-3000:]
    assert lines[-2]["failed"] == [] and lines[-2]["rehearsal_passed"]
    assert lines[-1]["ok"] is False and out.returncode != 0
