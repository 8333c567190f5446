"""Compile-only rehearsal for the chip: the Pallas kernels of the serving
path, at real widths, through the TPU compiler for a DESCRIBED v5e:2x2
topology (no chip attached; nothing executes).

Interpret mode (the rest of tier-1) proves kernel semantics, not that
Mosaic accepts the lowering: DMA slices not aligned to the (8,128) tiling,
VMEM budgets and shard_map partitioning only fail in the real compiler.
The caches are built by the adapter's own `init_kv`, so the head-dim
padding (`LlamaConfig.kv_head_dim`) that keeps the kernels legal for head
sizes that are not a 128 multiple is what these tests exercise.

`jax.default_backend()`-keyed branches (kernel `interpret` defaults,
`paged_write`'s kernel switch) are steered here, in the test; the program
has no option for it. A compile that passes is not a chip run —
`python chip_smoke.py` through the chip tool is.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.models.registry import get_model
from dynamo_tpu.ops.flash_prefill import (
    flash_prefill_attention,
    paged_prefill_attention,
)
from dynamo_tpu.ops.kv_update import paged_write
from dynamo_tpu.ops.paged_attention import paged_decode_attention

PAGES, PAGE = 512, 64  # the CLI's default pool
MAX_PAGES = 4096 // PAGE  # --max-context 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"v5e:2x2 topology cannot be described: {e}")


@pytest.fixture(autouse=True)
def tpu_branches_no_persistent_cache(monkeypatch):
    """Trace the TPU branches, and keep these compiles out of the
    persistent cache: an entry compiled for a described device is written
    but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _adapter(head_dim=None, **cfg_overrides):
    """llama3-1b (16 layers, hidden 2048, 32/8 heads of 64, bf16) with
    the Pallas path on; `head_dim` swaps in another head size."""
    adapter = get_model("llama3-1b", dtype="bfloat16", attention_impl="pallas")
    if head_dim is None and not cfg_overrides:
        return adapter
    from dynamo_tpu.models.registry import _llama_adapter

    cfg = dataclasses.replace(
        adapter.config, head_dim=head_dim or adapter.config.head_dim,
        **cfg_overrides,
    )
    return _llama_adapter(f"llama3-1b-hd{cfg.head_dim}", cfg)


def _on(sharding, tree):
    """Shapes of `tree` (arrays or ShapeDtypeStructs) placed by `sharding`
    (one Sharding for all leaves, or a matching tree of them)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding,
        )
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kv_shapes(adapter, kv_quantize):
    return jax.eval_shape(
        lambda: adapter.init_kv(PAGES, PAGE, kv_quantize=kv_quantize)
    )


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _kernel_calls(text: str, name: str) -> int:
    """Calls of the Pallas kernel `name` in an optimized program's text:
    a custom call is named after its kernel."""
    return len(re.findall(
        rf"%{name}(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text))


def _compile_decode(adapter, kv, chip, b=8):
    cfg = adapter.config
    d = cfg.kv_head_dim

    def fn(q, kv, pt, hist):
        return paged_decode_attention(
            q, kv.k, kv.v, jnp.int32(3), pt, hist, scale_dim=cfg.head_dim,
            k_scale=kv.k_scale, v_scale=kv.v_scale,
        )

    return jax.jit(fn).lower(
        _sds((b, cfg.num_heads, d), cfg.dtype, chip), _on(chip, kv),
        _sds((b, MAX_PAGES), jnp.int32, chip), _sds((b,), jnp.int32, chip),
    ).compile()


def _compile_write(adapter, kv, chip, b, t):
    cfg = adapter.config
    stage = _sds(
        (cfg.num_layers, b, t, cfg.num_kv_heads, cfg.kv_head_dim),
        cfg.dtype, chip,
    )

    def fn(kv, ks, vs, pt, pos, valid):
        return paged_write(
            kv.k, kv.v, ks, vs, pt, pos, valid,
            k_scale=kv.k_scale, v_scale=kv.v_scale,
        )

    return jax.jit(fn).lower(
        _on(chip, kv), stage, stage, _sds((b, MAX_PAGES), jnp.int32, chip),
        _sds((b, t), jnp.int32, chip), _sds((b, t), jnp.bool_, chip),
    ).compile()


def _compile_flash_prefill(adapter, chip, b=4, t=128):
    cfg = adapter.config
    d = cfg.kv_head_dim
    return jax.jit(
        lambda q, k, v, n: flash_prefill_attention(
            q, k, v, n, scale_dim=cfg.head_dim
        )
    ).lower(
        _sds((b, t, cfg.num_heads, d), cfg.dtype, chip),
        _sds((b, t, cfg.num_kv_heads, d), cfg.dtype, chip),
        _sds((b, t, cfg.num_kv_heads, d), cfg.dtype, chip),
        _sds((b,), jnp.int32, chip),
    ).compile()


def _paged_prefill_call(adapter, kv, chip, b=4, t=128):
    """(fn, its arguments' shapes) of one GQA history-chunk call, placed
    on `chip` or, with None, nowhere (a trace, not a compile)."""
    cfg = adapter.config
    d = cfg.kv_head_dim
    cur = _sds((b, t, cfg.num_kv_heads, d), cfg.dtype, chip)

    def fn(q, kc, vc, kv, pt, hist, cur_lens):
        return paged_prefill_attention(
            q, kc, vc, kv.k, kv.v, jnp.int32(3), pt, hist, cur_lens,
            scale_dim=cfg.head_dim, k_scale=kv.k_scale, v_scale=kv.v_scale,
        )

    return fn, (
        _sds((b, t, cfg.num_heads, d), cfg.dtype, chip), cur, cur,
        _on(chip, kv) if chip else kv, _sds((b, MAX_PAGES), jnp.int32, chip),
        _sds((b,), jnp.int32, chip), _sds((b,), jnp.int32, chip),
    )


def _compile_paged_prefill(adapter, kv, chip, b=4, t=128):
    fn, shapes = _paged_prefill_call(adapter, kv, chip, b, t)
    return jax.jit(fn).lower(*shapes).compile()


#: (head_dim override, extra config overrides): llama3-1b's own 64 lanes
#: through the padding; 128 (llama3-8b widths); 96 (phi3-mini's 32/32
#: heads of 96 — ROADMAP R1's candidates)
WIDTHS = {
    "hd64": (None, {}),
    "hd128": (128, {}),
    "hd96": (96, {"num_kv_heads": 32}),
}


KERNELS = (
    "decode", "write_decode", "write_prefill", "flash_prefill",
    "paged_prefill",
)
#: every kernel x width x page dtype, minus what adds nothing: flash_prefill
#: reads no pages, and paged_prefill keeps its int8 case at the model's own
#: width only — the scale planes do not depend on the head size
CASES = [
    (kernel, width, kvq)
    for kernel in KERNELS
    for width in WIDTHS
    for kvq in (None, "int8")
    if not (kvq and kernel == "flash_prefill")
    and not (kvq and kernel == "paged_prefill" and width != "hd64")
]


def _case(kernel, width, kvq):
    # paged_prefill costs ~9 s a compile whatever the shape: tier-1 keeps
    # it at the model's own width with bf16 pages (the decode kernel's
    # int8 cases cover the same scale-plane DMA in a second each); its
    # other cases run with -m slow
    slow = kernel == "paged_prefill" and (width, kvq) != ("hd64", None)
    return pytest.param(
        kernel, width, kvq, id=f"{kernel}-{width}-{kvq or 'bf16'}",
        marks=[pytest.mark.slow] if slow else [],
    )


@pytest.mark.parametrize(
    "kernel,width,kv_quantize", [_case(*c) for c in CASES]
)
def test_kernel_compiles_for_v5e(topo, kernel, width, kv_quantize):
    head_dim, overrides = WIDTHS[width]
    adapter = _adapter(head_dim, **overrides)
    assert adapter.config.kv_head_dim % 128 == 0
    chip = SingleDeviceSharding(topo.devices[0])
    kv = _kv_shapes(adapter, kv_quantize)
    if kernel == "decode":
        compiled = _compile_decode(adapter, kv, chip)
    elif kernel == "write_decode":
        compiled = _compile_write(adapter, kv, chip, b=8, t=1)
    elif kernel == "write_prefill":
        compiled = _compile_write(adapter, kv, chip, b=4, t=128)
    elif kernel == "flash_prefill":
        compiled = _compile_flash_prefill(adapter, chip)
    else:
        compiled = _compile_paged_prefill(adapter, kv, chip)
    assert _mosaic_calls(compiled) >= 1


@pytest.mark.parametrize(
    "tp,kv_quantize", [(4, None), (2, "int8")], ids=["tp4-bf16", "tp2-int8"]
)
@pytest.mark.parametrize("kernel", ["decode", "write_decode"])
def test_kernel_compiles_inside_tp_shard_map(topo, kernel, tp, kv_quantize):
    """The decode and write kernels under their tp shard_map on a Mesh of
    the described chips (llama3-1b: 32/8 heads divide by 4). Narrow pages
    need their kv heads per shard in fours (Mosaic packs four 8-bit rows
    per sublane), so int8 stops at tp=2 here — JaxEngine refuses the rest
    (test_engine_refuses_narrow_pages_with_too_few_kv_heads_per_shard)."""
    mesh = Mesh(
        np.asarray(topo.devices[:tp]).reshape(1, 1, 1, tp),
        axis_names=("dp", "sp", "ep", "tp"),
    )
    adapter = get_model(
        "llama3-1b", dtype="bfloat16", attention_impl="pallas", mesh=mesh
    )
    cfg = adapter.config
    rep = NamedSharding(mesh, P())
    kv = _on(
        jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            adapter.kv_spec(kv_quantize=kv_quantize),
        ),
        _kv_shapes(adapter, kv_quantize),
    )
    b, d = 8, cfg.kv_head_dim
    pt = _sds((b, MAX_PAGES), jnp.int32, rep)
    if kernel == "decode":
        def fn(q, kv, pt, hist):
            return paged_decode_attention(
                q, kv.k, kv.v, jnp.int32(3), pt, hist,
                scale_dim=cfg.head_dim, mesh=mesh,
                k_scale=kv.k_scale, v_scale=kv.v_scale,
            )

        compiled = jax.jit(fn).lower(
            _sds((b, cfg.num_heads, d), cfg.dtype,
                 NamedSharding(mesh, P(None, "tp", None))),
            kv, pt, _sds((b,), jnp.int32, rep),
        ).compile()
    else:
        stage = _sds(
            (cfg.num_layers, b, 1, cfg.num_kv_heads, d), cfg.dtype,
            NamedSharding(mesh, P(None, None, None, "tp", None)),
        )

        def fn(kv, ks, vs, pt, pos, valid):
            return paged_write(
                kv.k, kv.v, ks, vs, pt, pos, valid, mesh=mesh,
                k_scale=kv.k_scale, v_scale=kv.v_scale,
            )

        compiled = jax.jit(fn).lower(
            kv, stage, stage, pt, _sds((b, 1), jnp.int32, rep),
            _sds((b, 1), jnp.bool_, rep),
        ).compile()
    assert _mosaic_calls(compiled) >= 1


def test_whole_llama3_1b_decode_step_compiles_with_both_kernels(topo):
    """One full decode step (16 layers, forward + logits + argmax, B=8)
    at the published Llama-3.2-1B widths: the page-walk and the DMA
    writer are both in the compiled program."""
    adapter = _adapter()
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(
        chip,
        jax.eval_shape(lambda: adapter.init_params(jax.random.key(0))),
    )
    b = 8

    def step(params, tokens, positions, valid, kv, pt):
        hidden, kv = adapter.forward_hidden(
            params, tokens, positions, valid, kv, pt
        )
        logits = adapter.compute_logits(params, hidden[:, -1])
        return jnp.argmax(logits, axis=-1), kv

    compiled = jax.jit(step, donate_argnums=(4,)).lower(
        params, _sds((b, 1), jnp.int32, chip), _sds((b, 1), jnp.int32, chip),
        _sds((b, 1), jnp.bool_, chip), _on(chip, _kv_shapes(adapter, None)),
        _sds((b, MAX_PAGES), jnp.int32, chip),
    ).compile()
    assert _mosaic_calls(compiled) == 2


@pytest.mark.parametrize("rows,t,first_chunk", [
    pytest.param(64, 1, False, id="decode-64-rows"),
    pytest.param(1, 512, False, id="prefill-chunk-over-latent-history"),
    pytest.param(2, 32, False, id="prefill-tail-shorter-than-a-page"),
    pytest.param(4, 512, False, id="four-prompts-side-by-side"),
])
def test_deepseek_v2_lite_step_compiles_at_published_widths(
        topo, rows, t, first_chunk):
    """One whole step of `deepseek-v2-lite-8l` as `dsv2lite-docgen`
    serves it, with a larger pool (bf16, 6200 pages against the cell's 5000,
    --max-context 8192): the latent page
    walk (in both layer scans), the grouped matmuls and the cache writers
    go through the TPU compiler, the program fits the chip beside 13.25
    GB of weights and cache, and nothing copies the KV pool (one scatter
    over all layers did, and so did a loop of dynamic_update_slice inside
    the fused decode scan: 4 GB of temporaries). A prefill chunk attends
    over its latent history in ONE kernel (once in each layer scan): no
    float32 score, weight or accumulator tensor of the XLA loop it
    replaced ([B, 16, T, 512]: 16 MB each at T 512) is left in the
    program, nor the 0.4 GB copy of a layer's pool that loop sliced out,
    and the temporaries lie under that loop's (PR 38's tree compiled
    here: 550.8 MB at 1 x 512, 520.1 at 2 x 32, 784.8 at 4 x 512; this
    tree 33.6, 6.0 and 309.6)."""
    adapter = get_model("deepseek-v2-lite-8l", dtype="bfloat16",
                        attention_impl="pallas")
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(
        lambda: adapter.init_params(jax.random.key(0))))
    kv = _on(chip, jax.eval_shape(lambda: adapter.init_kv(6200, PAGE)))
    assert kv.k.shape[-1] == 512 and kv.v.shape[-1] == 128

    def step(tokens, positions, kv, params, valid, pt):
        hidden, kv = adapter.forward_hidden(
            params, tokens, positions, valid, kv, pt,
            first_chunk=first_chunk,
        )
        logits = adapter.compute_logits(params, hidden[:, -1])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), kv

    def program(params, tokens, positions, valid, kv, pt):
        if t > 1:
            return step(tokens, positions, kv, params, valid, pt)

        # decode as the engine fuses it (`multi_fn`): 8 steps in one scan,
        # the pool its carry. A carry is where XLA is free to pick another
        # layout for the pool and copy it in and out of the loop.
        def body(carry, _):
            tokens, positions, kv = carry
            ids, kv = step(tokens, positions, kv, params, valid, pt)
            return (ids[:, None], positions + 1, kv), ids

        (_, _, kv), ids = jax.lax.scan(
            body, (tokens, positions, kv), None, length=8)
        return ids, kv

    compiled = jax.jit(program, donate_argnums=(4,)).lower(
        params, _sds((rows, t), jnp.int32, chip),
        _sds((rows, t), jnp.int32, chip), _sds((rows, t), jnp.bool_, chip),
        kv, _sds((rows, 8192 // PAGE), jnp.int32, chip),
    ).compile()
    mem = compiled.memory_analysis()
    pool = 8 * 6200 * PAGE * (512 + 128) * 2
    assert mem.alias_size_in_bytes >= pool  # the cache is updated in place
    assert mem.temp_size_in_bytes < 2.2e9  # < 0.8 GB of the cache's own size
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    text = compiled.as_text()
    if t == 1:  # the walk in both layer scans, three gmm, the row writer
        assert text.count("paged_decode_attention") >= 2
        assert "paged_kv_write_rows" in text
        assert mem.temp_size_in_bytes < 0.8e9
    else:  # whole pages of the chunk through the DMA writer
        assert "paged_kv_write" in text
        assert len(re.findall(
            r"%latent_prefill_attention[.\d]* = \S+ custom-call", text)) == 2
        assert not re.search(r"f32\[\d+,16,\d+,512\]", text)
        assert mem.temp_size_in_bytes < {1: 100e6, 2: 100e6, 4: 400e6}[rows]
    # three grouped matmuls and a cache writer, and the attention kernel of
    # the kind of step in both layer scans
    assert _mosaic_calls(compiled) >= 6


def _primitives(jaxpr, seen):
    """Count of every primitive in `jaxpr` and the jaxprs under it, a dot
    besides under its operands' dtypes."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        seen[name] = seen.get(name, 0) + 1
        if name == "dot_general":
            key = "dot:" + "/".join(str(v.aval.dtype) for v in eqn.invars)
            seen[key] = seen.get(key, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, seen)
    return seen


def _shapes(jaxpr, seen: set):
    """(primitive, shape) of every value `jaxpr` and the jaxprs under it
    bind."""
    for eqn in jaxpr.eqns:
        seen.update((eqn.primitive.name, tuple(v.aval.shape))
                    for v in eqn.outvars if hasattr(v.aval, "shape"))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _shapes(sub, seen)
    return seen


@pytest.mark.parametrize("kvq", [None, "int8"])
def test_gqa_history_kernel_is_what_it_was_before_the_latent_one(kvq):
    """`ops/flash_prefill.py` gained a latent kernel beside `_hist_kernel`
    (PR 39); the four cells that run `paged_prefill_attention` (qwen2,
    phi3, nemotron-h, falcon-h1: rotary or plain GQA over dense pages)
    must trace to the kernel they traced to on PR 38's tree, read here
    from the jaxpr at llama shapes: one kernel of that name, a cell a
    prompt, ONE page a slot of its double buffer, float32 operands at
    every dot (the latent kernel's bf16 operands and its block of 8
    pages are a body of its own), the same 100 MB limit."""
    adapter = _adapter()
    fn, shapes = _paged_prefill_call(
        adapter, _kv_shapes(adapter, kvq), None)
    jaxpr = jax.make_jaxpr(fn)(*shapes)
    assert "latent" not in str(jaxpr)
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    assert call.params["name"] == "paged_prefill_attention"
    assert grid.grid == (4, 1) and grid.num_index_operands == 4
    assert call.params["compiler_params"][
        "mosaic_tpu"].vmem_limit_bytes == 100 * 1024 * 1024
    body = call.params["jaxpr"]
    scratch = [str(v.aval) for v in
               body.invars[-grid.num_scratch_operands:]]
    page = {None: "bfloat16", "int8": "int8"}[kvq] + "[2,64,8,128]"
    scales = ["Ref<vmem>{float32[2,8,128]}"] * 2 if kvq else []
    planes = 4 if kvq else 2
    assert scratch == [f"Ref<vmem>{{{page}}}"] * 2 + scales + [
        f"Ref<semaphore_mem>{{dma_sem[{planes},2]}}"]
    seen = _primitives(body, {})
    assert seen["dot_general"] == seen["dot:float32/float32"] == 32
    assert (seen["dma_start"], seen["dma_wait"]) == (2 * planes, planes)
    assert (seen["exp"], seen["while"], seen["cond"]) == (32, 2, 2)


def test_nemotron3_nano_state_comparison_compiles_at_published_widths(topo):
    """What `correct` runs beside the log-probs (chipbench/references/
    nemotron_h.py `served_states`): 64 tokens of ONE row through the
    decode kernel inside a scan, in a pool of one slot, the entries
    traced."""
    import importlib.util

    from chipbench import manifest

    spec = importlib.util.spec_from_file_location(
        "ref_nemotron_h_tpu",
        manifest.ROOT / "chipbench/references/nemotron_h.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    adapter = get_model("nemotron3-nano-28l-16e", dtype="bfloat16",
                        attention_impl="pallas")
    chip = SingleDeviceSharding(topo.devices[0])
    pool = _on(chip, jax.eval_shape(
        lambda: adapter.init_kv(2, 1, state_slots=1))).ssm
    assert pool.shape == (12, 4, 64, 64, 128) and pool.dtype == jnp.float32
    f32 = jnp.float32
    tr = {"start": _sds((64, 64, 128), f32, chip),
          "u": _sds((64, 64, 64), f32, chip),
          "decay": _sds((64, 64), f32, chip),
          "b": _sds((64, 8, 128), f32, chip),
          "c": _sds((64, 8, 128), f32, chip)}
    compiled = jax.jit(ref.decode_through_the_pool).lower(
        pool, _sds((), jnp.int32, chip), tr).compile()
    assert "ssm_decode_step" in compiled.as_text()


#: (preset, pages, state slots, --max-context, ssm pool, conv pool)
_NANO3 = ("nemotron3-nano-28l-16e", 3200, 72, 4096,
          (12, 146, 64, 64, 128), (12, 146, 144, 128))
_FALCON = ("falcon-h1-34b-6l", 3300, 36, 8192,
           (6, 74, 32, 128, 256), (6, 74, 120, 128))


@pytest.mark.parametrize("served,rows,t,b_pre", [
    pytest.param(_NANO3, 64, 1, 0, id="decode-64-rows-fused-8"),
    pytest.param(_NANO3, 1, 512, 0, id="prefill-chunk-from-a-slot"),
    pytest.param(_NANO3, 64, 128, 1, id="mixed-64-rows-beside-a-chunk"),
    pytest.param(_FALCON, 32, 1, 0, id="falcon-h1-decode-32-rows-fused-8"),
    pytest.param(_FALCON, 32, 512, 1,
                 id="falcon-h1-mixed-32-rows-beside-a-chunk"),
    pytest.param(_FALCON, 32, 512, 2,
                 id="falcon-h1-mixed-32-rows-beside-two-pieces"),
    pytest.param(_FALCON, 32, 512, 4,
                 id="falcon-h1-mixed-32-rows-beside-four-pieces"),
])
def test_hybrid_cut_step_compiles_at_published_widths(
        topo, served, rows, t, b_pre):
    """Whole steps of `nemotron3-nano-28l-16e` as `nano3-chat-churn`
    serves it (bf16, 3200 pages, 72 state slots in two generations,
    --max-context 4096): the state kernel and the row writer of the
    Mamba-2 layers, the page walk at 16 query heads a KV head, the grouped
    matmul at 2688 x 1920 (1856 padded to whole lanes) go through the TPU
    compiler inside the scan over units of seven layers; both pools are
    updated in place, and the program fits the chip beside 6.9 GB of
    weights, 3.8 GB of state and 0.84 GB of pages. And of
    `falcon-h1-34b-6l` as `falconh1-longdoc` serves it (bf16, 3300 pages,
    36 slots, --max-context 8192): the state kernel blocked over heads
    (one group of 16 heads x 128 x 256 a grid step), the row writer moving
    4.19 MB rows, the page walk at 5 query heads a KV head and the flash
    chunk over paged history in every layer of a plain scan, beside 10.5
    GB of weights, 1.9 GB of state and 2.6 GB of pages."""
    preset, pages, slots, context, ssm_shape, conv_shape = served
    adapter = get_model(preset, dtype="bfloat16", attention_impl="pallas")
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(
        lambda: adapter.init_params(jax.random.key(0))))
    kv = _on(chip, jax.eval_shape(
        lambda: adapter.init_kv(pages, PAGE, state_slots=slots)))
    assert kv.ssm.shape == ssm_shape
    assert kv.conv.shape == conv_shape
    mp = context // PAGE

    def rows_of(b, tt):
        return (
            _sds((b, tt), jnp.int32, chip), _sds((b, tt), jnp.int32, chip),
            _sds((b, tt), jnp.bool_, chip),
            (_sds((b, mp), jnp.int32, chip), _sds((b, 2), jnp.int32, chip)),
        )

    def head(params, hidden):
        logits = adapter.compute_logits(params, hidden[:, -1])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if b_pre:  # the fused mixed step: a prompt chunk beside the decode rows

        def program(params, kv, prompt, decode):
            h_p, h_d, kv = adapter.forward_hidden_mixed(
                params, prompt, decode, kv)
            return head(params, h_d), kv

        args = (rows_of(b_pre, t), rows_of(rows, 1))
    elif t > 1:

        def program(params, kv, tokens, positions, valid, pt):
            hidden, kv = adapter.forward_hidden(
                params, tokens, positions, valid, kv, pt)
            return head(params, hidden), kv

        args = rows_of(rows, t)
    else:  # decode as the engine fuses it: 8 steps, the pools the carry,
        # the state read at one entry in the first step and then where the
        # step before wrote it

        def program(params, kv, tokens, positions, valid, pt):
            def body(carry, _):
                tokens, positions, kv, pt = carry
                hidden, kv = adapter.forward_hidden(
                    params, tokens, positions, valid, kv, pt)
                ids = head(params, hidden)
                pt = (pt[0], jnp.broadcast_to(pt[1][:, 1:], pt[1].shape))
                return (ids[:, None], positions + 1, kv, pt), ids

            (_, _, kv, _), ids = jax.lax.scan(
                body, (tokens, positions, kv, pt), None, length=8)
            return ids, kv

        args = rows_of(rows, t)

    compiled = jax.jit(program, donate_argnums=(1,)).lower(
        params, kv, *args).compile()
    mem = compiled.memory_analysis()
    pools = sum(np.prod(x.shape) * x.dtype.itemsize
                for x in (kv.k, kv.v, kv.conv, kv.ssm))
    assert mem.alias_size_in_bytes >= pools  # both caches updated in place
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    text = compiled.as_text()
    assert "state_write_rows" in text
    if t > 1:  # a chunk starts from its slot's state: one DMA a row, and
        # no temporary the size of the pool (XLA's gather made two)
        assert "state_read_rows" in text
        assert mem.temp_size_in_bytes < 1.2e9
    if t == 1 or b_pre:
        assert "ssm_decode_step" in text
        assert "paged_decode_attention" in text
    assert _mosaic_calls(compiled) >= 4


def test_gqa_decode_walk_is_what_it_was_before_the_selecting_one():
    """PR 41 makes the decode walk read a CHOSEN part of a row (a list of
    pages a KV head, models/minicpm_sala.py) without a line of
    `ops/paged_attention.py`: a KV head of such a model is a row of its
    own over a one-row cache. The five other cells' page lists stay per
    ROW, and a decode program of `qwen2-7b` traces to the walk it traced
    to on PR 39's tree, read here from the jaxpr at its served widths (64
    rows, 28 / 4 heads of 128, int8 weights aside): ONE kernel of that
    name in a layer, five prefetched scalars of which the flat page
    tables hold rows x max pages entries, the whole batch's q and acc
    resident, two slots of 8 pages x 64 x 4 rows, two dots a block."""
    adapter = get_model("qwen2-7b", dtype="bfloat16",
                        attention_impl="pallas")
    cfg = adapter.config
    b, mp, pages = 64, 8192 // PAGE, 1700
    kv = jax.eval_shape(lambda: adapter.init_kv(pages, PAGE))
    q = jax.ShapeDtypeStruct((b, cfg.num_heads, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, pt, hist: paged_decode_attention(
            q, k, v, jnp.int32(0), pt, hist, scale_dim=128)
    )(q, kv.k, kv.v, jax.ShapeDtypeStruct((b, mp), jnp.int32),
      jax.ShapeDtypeStruct((b,), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    assert call.params["name"] == "paged_decode_attention"
    assert grid.grid == (1,) and grid.num_index_operands == 5
    shapes = [str(v.aval) for v in call.invars]
    assert shapes[:5] == ["int32[1]", "int32[1]", f"int32[{b}]",
                          f"int32[{b * mp}]", f"int32[{b}]"]
    assert shapes[5:] == [
        "bfloat16[64,32,128]",  # 28 query heads in whole sublane tiles
        f"bfloat16[28,{pages},256,128]", f"bfloat16[28,{pages},256,128]"]
    body = call.params["jaxpr"]
    scratch = [str(v.aval) for v in
               body.invars[-grid.num_scratch_operands:]]
    assert scratch == ["Ref<vmem>{bfloat16[2,2048,128]}"] * 2 + [
        "Ref<semaphore_mem>{dma_sem[2,2]}"]
    seen = _primitives(body, {})
    assert seen["dot_general"] == seen["dot:bfloat16/bfloat16"] == 2
    assert (seen["dma_start"], seen["dma_wait"]) == (4, 2)
    # and the selecting walk IS that kernel: a KV head a row, 16 query
    # heads, a list of at most 128 pages of one row each
    sala = get_model("minicpm-sala-9b-16l", dtype="bfloat16",
                     attention_impl="pallas")
    skv = jax.eval_shape(lambda: sala.init_kv(9000, PAGE, state_slots=36))
    assert skv.k.shape == (4, 18000, 64, 1, 128)
    assert skv.kc.shape == (4, 72000, 128)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, pt, hist: paged_decode_attention(
            q, k, v, jnp.int32(0), pt, hist, scale_dim=128)
    )(jax.ShapeDtypeStruct((64, 16, 128), jnp.bfloat16), skv.k, skv.v,
      jax.ShapeDtypeStruct((64, 128), jnp.int32),
      jax.ShapeDtypeStruct((64,), jnp.int32))
    (walk,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert walk.params["name"] == "paged_decode_attention"
    assert _primitives(walk.params["jaxpr"], {}) == seen


@pytest.mark.parametrize("rows,t,b_pre", [
    pytest.param(32, 1, 0, id="decode-32-rows-fused-8"),
    # (PR 56: 90 and 82 test-seconds; a builder with the chip runs them on
    # demand, `-m slow`, before a call that touches this family)
    pytest.param(32, 512, 1, id="mixed-32-rows-beside-a-chunk",
                 marks=pytest.mark.slow),
    pytest.param(32, 512, 4, id="mixed-32-rows-beside-four-pieces",
                 marks=pytest.mark.slow),
])
def test_minicpm_sala_step_compiles_at_published_widths(
        topo, rows, t, b_pre):
    """Whole steps of `minicpm-sala-9b-16l` as `sala-longctx` serves it
    (bf16, 9,000 pages of 64 with their compressed keys, 36 state slots in
    two generations, --max-context 18432): the page walk over a selected
    list at one KV head a row (64 virtual rows, 16 query heads), the
    single-row cache writer, the state kernel at one group a head (the
    whole 2.1 MB row a grid step), the row writer and reader of a prompt
    chunk's state go through the TPU compiler inside ONE body a kind of
    layer (a scan over the sparse layers, a loop over the lightning layers
    that follow each); every pool is updated in place; the program
    fits the chip beside 10.08 GB of weights, 1.86 GB of state and 2.43 GB
    of pages. The temporaries stay small: without the barrier in
    `models/minicpm_sala._heads` the compiler transposed q, k and v of
    every layer into 1.38 GB of copies ahead of the layer loops. A prompt
    chunk attends by tile of queries in a kernel of its own (PR 42,
    ops/sparse_chunk.py), dense or past `dense_len`: the decode kernel
    stays the decode rows' alone (on PR 41's tree a sparse chunk's 1,024
    queries walked a list each through a second one, and a dense chunk
    ran the latent kernel). Since PR 46 the selection reads the
    compressed keys out of the pool in place: the kernel
    `paged_block_scores` (ops/block_scores.py) stands in the decode
    program once and in a mixed program twice (the prompt's rows and the
    decode rows'), no program traces a sort or a gathered copy of the
    rows' compressed keys ([64, 1152, 128] bf16, 18.9 MB a layer), and
    the temporaries fell with the float32 scores a piece held:
    11,916,800 bytes beside one piece (186,084,864 on PR 45's tree),
    163,373,568 beside four (833,350,144), 77,358,592 for the fused
    decode program (77,313,536: its largest live set was never the
    copy)."""
    adapter = get_model("minicpm-sala-9b-16l", dtype="bfloat16",
                        attention_impl="pallas")
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(
        lambda: adapter.init_params(jax.random.key(0))))
    kv = _on(chip, jax.eval_shape(
        lambda: adapter.init_kv(9000, PAGE, state_slots=36)))
    assert kv.ssm.shape == (12, 74, 32, 128, 128) and kv.conv is None
    mp = 18432 // PAGE

    def rows_of(b, tt):
        return (
            _sds((b, tt), jnp.int32, chip), _sds((b, tt), jnp.int32, chip),
            _sds((b, tt), jnp.bool_, chip),
            (_sds((b, mp), jnp.int32, chip), _sds((b, 2), jnp.int32, chip)),
        )

    def head(params, hidden):
        logits = adapter.compute_logits(params, hidden[:, -1])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if b_pre:

        def program(params, kv, prompt, decode):
            h_p, h_d, kv = adapter.forward_hidden_mixed(
                params, prompt, decode, kv)
            return head(params, h_d), kv

        args = (rows_of(b_pre, t), rows_of(rows, 1))
    else:

        def program(params, kv, tokens, positions, valid, pt):
            def body(carry, _):
                tokens, positions, kv, pt = carry
                hidden, kv = adapter.forward_hidden(
                    params, tokens, positions, valid, kv, pt)
                ids = head(params, hidden)
                pt = (pt[0], jnp.broadcast_to(pt[1][:, 1:], pt[1].shape))
                return (ids[:, None], positions + 1, kv, pt), ids

            (_, _, kv, _), ids = jax.lax.scan(
                body, (tokens, positions, kv, pt), None, length=8)
            return ids, kv

        args = rows_of(rows, t)

    traced = jax.jit(program, donate_argnums=(1,)).trace(params, kv, *args)
    shapes = _shapes(traced.jaxpr.jaxpr, set())
    # the top 64 and the walk's list by counting: nothing sorts a row's
    # blocks (the walk's work list sorts its 64 rows), and nothing holds
    # a copy of a row's compressed keys
    assert not any(p == "sort" and s[-1:] == (mp,) for p, s in shapes)
    assert not any(s[-2:] == (mp * 4, 128) for _, s in shapes)
    compiled = traced.lower().compile()
    mem = compiled.memory_analysis()
    pools = sum(np.prod(x.shape) * x.dtype.itemsize
                for x in (kv.k, kv.v, kv.kc, kv.ssm))
    assert mem.alias_size_in_bytes >= pools  # every pool in place
    assert mem.temp_size_in_bytes <= {0: 96e6, 1: 24e6, 4: 200e6}[b_pre]
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    text = compiled.as_text()
    assert "ssm_decode_step" in text and "paged_decode_attention" in text
    if t > 1:
        assert "state_read_rows" in text and "state_write_rows" in text
        assert _kernel_calls(text, "sparse_chunk_attention") == 1
        assert _kernel_calls(text, "latent_prefill_attention") == 0
    assert _kernel_calls(text, "paged_decode_attention") == 1
    assert _kernel_calls(text, "paged_block_scores") == (2 if b_pre else 1)
    # one body a kind: the selection's scores, the walk, the page writer
    # and the state kernel of a decode step; a chunk adds its kernels and
    # its state rows
    assert _mosaic_calls(compiled) >= (8 if b_pre else 4)


@pytest.mark.parametrize("rows,t,b_pre", [
    pytest.param(32, 1, 0, id="decode-32-rows-fused-8"),
    pytest.param(32, 512, 1, id="mixed-32-rows-beside-a-chunk"),
    pytest.param(32, 32, 32, id="mixed-32-rows-beside-32-short-prompts"),
])
def test_keye_vl_step_compiles_at_published_widths(topo, rows, t, b_pre):
    """Whole steps of `keye-vl2-30b-a3b-8l-16e` as `keye-longctx` serves
    it (bf16, 9,000 pages of 64 with their index keys, --max-context
    18432): the index keys gathered through the page tables and landed
    as one scatter of rows with no copy of their pool, the scores and the
    sort-free selection, the decode rows' page walk under a bit a token
    (`token_bits`: 9.4 MB of int32 columns resident in VMEM), a prompt
    chunk's token-mask kernel taking the pools as the cache lays them out
    (ops/sparse_chunk.py `token_chunk_attention`), the
    grouped matmuls over the 16 held experts reading their layer of the
    stack in place, and the staged K and V landed once: every pool
    updated in place, the program beside 2.79 GB of weights and 10.03 GB
    of pages inside the chip."""
    adapter = get_model("keye-vl2-30b-a3b-8l-16e", dtype="bfloat16",
                        attention_impl="pallas")
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(
        lambda: adapter.init_params(jax.random.key(0))))
    kv = _on(chip, jax.eval_shape(lambda: adapter.init_kv(9000, PAGE)))
    assert kv.ki.shape == (4, 9000, PAGE, 128)
    mp = 18432 // PAGE

    def rows_of(b, tt):
        return (
            _sds((b, tt), jnp.int32, chip), _sds((b, tt), jnp.int32, chip),
            _sds((b, tt), jnp.bool_, chip), _sds((b, mp), jnp.int32, chip),
        )

    def head(params, hidden):
        logits = adapter.compute_logits(params, hidden[:, -1])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if b_pre:

        def program(params, kv, prompt, decode):
            h_p, h_d, kv = adapter.forward_hidden_mixed(
                params, prompt, decode, kv)
            return head(params, h_d), kv

        args = (rows_of(b_pre, t), rows_of(rows, 1))
    else:

        def program(params, kv, tokens, positions, valid, pt):
            def body(carry, _):
                tokens, positions, kv = carry
                hidden, kv = adapter.forward_hidden(
                    params, tokens, positions, valid, kv, pt)
                ids = head(params, hidden)
                return (ids[:, None], positions + 1, kv), ids

            (_, _, kv), ids = jax.lax.scan(
                body, (tokens, positions, kv), None, length=8)
            return ids, kv

        args = rows_of(rows, t)

    compiled = jax.jit(program, donate_argnums=(1,)).lower(
        params, kv, *args).compile()
    mem = compiled.memory_analysis()
    pools = sum(np.prod(x.shape) * x.dtype.itemsize
                for x in (kv.k, kv.v, kv.ki))
    assert mem.alias_size_in_bytes >= pools  # every pool in place
    assert mem.temp_size_in_bytes <= 1.2e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    text = compiled.as_text()
    # ONE body: a chunk's token-mask kernel, and the decode rows' page walk
    # (under a bit a token: the gather of chosen rows is gone)
    assert _kernel_calls(text, "token_chunk_attention") == (1 if b_pre else 0)
    assert _kernel_calls(text, "paged_decode_attention") == 1
    # 204 / 52 / 451 MB of temporaries since the gathered keys went
    assert mem.temp_size_in_bytes <= {0: 0.25e9, 1: 0.1e9, 32: 0.5e9}[b_pre]
    # the index scores read the pool in place (PR 45, ops/index_scores.py):
    # one kernel for the decode rows, one for a chunk's tiles, and neither
    # the gathered copy of the rows' keys with the other layer's half
    # ([B, MP x S, 128], 151 MB at 32 rows) nor a chunk row's is built
    assert _kernel_calls(text, "paged_index_scores") == 1
    assert _kernel_calls(text, "paged_index_scores_chunk") == (
        1 if b_pre else 0)
    for copy in (f"bf16[{rows},{mp * PAGE},128]",
                 f"bf16[{rows},{mp},{PAGE},128]",
                 f"bf16[{b_pre},{mp * PAGE},128]",
                 f"bf16[{b_pre},{mp},{PAGE},128]",
                 f"bf16[1,{mp * PAGE},128]", f"bf16[1,{mp},{PAGE},128]",
                 f"bf16[{rows},{mp * PAGE},64]",
                 f"bf16[{b_pre},{mp * PAGE},64]", f"bf16[1,{mp * PAGE},64]"):
        assert copy not in text, copy


@pytest.mark.parametrize("rows,t,b_pre", [
    pytest.param(32, 1, 0, id="decode-32-rows-fused-8"),
    # (PR 56: 100 and 112 test-seconds; on demand, `-m slow`)
    pytest.param(32, 512, 4, id="mixed-32-rows-beside-four-chunks",
                 marks=pytest.mark.slow),
    pytest.param(32, 32, 32, id="mixed-32-rows-beside-32-short-prompts",
                 marks=pytest.mark.slow),
])
def test_dots3_step_compiles_at_published_widths(topo, rows, t, b_pre):
    """Whole steps of `dots3-note-prev-9l-8e` as `dots3-longctx` serves it
    (bf16, 9,000 pages of 64 in 3 full layers with their index keys, 36
    ring slots of 1,088 rows in 6 window layers, --max-context 18432): the
    index scores read out of the one-row pool in place (64 heads), the
    sort-free selection, the decode rows' LATENT page walk under a bit a
    token (128 heads: in pieces that fit the kernel's VMEM budget), a
    prompt piece's plain-form kernel over the latent pages under a mask
    bit a (query, key), the
    window layers' ring written by position, walked by a decode row (and
    by a 32-token piece, absorbed) and read by page for a 512-token
    piece's plain-form banded kernel, the grouped matmuls over the 8 held experts: every pool updated in place, the
    program beside 6.2 GB of weights, 2.4 GB of pages and 0.5 GB of rings
    inside the chip."""
    adapter = get_model("dots3-note-prev-9l-8e", dtype="bfloat16",
                        attention_impl="pallas")
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(
        lambda: adapter.init_params(jax.random.key(0))))
    kv = _on(chip, jax.eval_shape(
        lambda: adapter.init_kv(9000, PAGE, state_slots=36)))
    assert kv.ki.shape == (3, 9000, PAGE, 128)
    assert kv.k.shape == (3, 9000, PAGE, 1, 512)
    assert kv.ring.shape == (6, 37, 1088, 1024)
    assert kv.ring_pe.shape == (6, 37, 1088, 128)
    mp = 18432 // PAGE

    def rows_of(b, tt):
        return (
            _sds((b, tt), jnp.int32, chip), _sds((b, tt), jnp.int32, chip),
            _sds((b, tt), jnp.bool_, chip),
            (_sds((b, mp), jnp.int32, chip), _sds((b, 2), jnp.int32, chip)),
        )

    def head(params, hidden):
        logits = adapter.compute_logits(params, hidden[:, -1])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if b_pre:

        def program(params, kv, prompt, decode):
            h_p, h_d, kv = adapter.forward_hidden_mixed(
                params, prompt, decode, kv)
            return head(params, h_d), kv

        args = (rows_of(b_pre, t), rows_of(rows, 1))
    else:

        def program(params, kv, tokens, positions, valid, pt):
            def body(carry, _):
                tokens, positions, kv = carry
                hidden, kv = adapter.forward_hidden(
                    params, tokens, positions, valid, kv, pt)
                ids = head(params, hidden)
                return (ids[:, None], positions + 1, kv), ids

            (_, _, kv), ids = jax.lax.scan(
                body, (tokens, positions, kv), None, length=8)
            return ids, kv

        args = rows_of(rows, t)

    compiled = jax.jit(program, donate_argnums=(1,)).lower(
        params, kv, *args).compile()
    mem = compiled.memory_analysis()
    pools = sum(np.prod(x.shape) * x.dtype.itemsize
                for x in (kv.k, kv.v, kv.ki, kv.ring, kv.ring_pe))
    print("dots3 compile", rows, t, b_pre, "temp", mem.temp_size_in_bytes,
          "args", mem.argument_size_in_bytes, "alias",
          mem.alias_size_in_bytes, "pools", pools)
    assert mem.alias_size_in_bytes >= pools  # every pool in place
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    text = compiled.as_text()
    # ONE body a kind of layer: the decode walk once a full layer's body
    # and once a window layer's (a decode row's ring walked as the latent
    # pages it is); a prompt piece attends a full layer PLAIN, its keys and
    # values up-projected inside `latent_plain_attention`; in a window layer
    # a 512-token piece attends plain too, under the banded kernel, and a
    # piece short beside the ring rows in reach (32 tokens beside 576)
    # runs the absorbed chunk kernel, which no full layer does any more
    plain = bool(b_pre) and 4 * t >= 576
    print({k: _kernel_calls(text, k) for k in (
        "latent_prefill_attention", "latent_plain_attention",
        "window_prefill_attention", "paged_decode_attention",
        "paged_index_scores", "paged_index_scores_chunk")})
    assert _kernel_calls(text, "latent_plain_attention") == int(bool(b_pre))
    assert _kernel_calls(text, "latent_prefill_attention") == int(
        bool(b_pre) and not plain)
    assert _kernel_calls(text, "window_prefill_attention") == int(plain)
    assert _kernel_calls(text, "paged_decode_attention") == 2
    assert _kernel_calls(text, "paged_index_scores") == 1
    assert _kernel_calls(text, "paged_index_scores_chunk") == (
        1 if b_pre else 0)
    # no copy of a pool: neither a layer of the rings nor the index keys
    for copy in ("bf16[6,37,1088,1024]", "bf16[37,1088,1024]",
                 "bf16[6,629,64,1,1024]", "bf16[32,1088,1024]",
                 "bf16[3,9000,64,128]", "bf16[9000,64,128]",
                 "bf16[3,9000,64,1,512]", "bf16[9000,64,1,512]",
                 f"bf16[{rows},{mp * PAGE},128]"):
        assert not re.search(
            rf"= {re.escape(copy)}[^ ]* copy\(", text), copy


@pytest.mark.parametrize("b,t", [
    pytest.param(4, 512, id="four-pieces-two-query-tiles-each"),
    pytest.param(1, 256, id="one-tile-of-256"),
    pytest.param(1, 192, id="one-tile-of-a-bucket-no-multiple-of-128"),
])
def test_window_band_kernel_compiles_at_published_widths(topo, b, t):
    """The banded plain-form kernel of a dots3 window layer's prompt piece
    (ops/flash_prefill.py `window_prefill_attention`) at the published 64
    heads of 256 | 128 in bf16, 576 ring columns and the piece's rows in
    whole 128-key blocks: a cell's keys and values inside VMEM, the
    columns of a tile read at a lane-aligned offset."""
    from dynamo_tpu.ops.flash_prefill import window_prefill_attention

    chip = SingleDeviceSharding(topo.devices[0])
    kk = -(-(576 + t) // 128) * 128
    bf = jnp.bfloat16
    text = jax.jit(functools.partial(
        window_prefill_attention, window=513, interpret=False)).lower(
        _sds((b, t, 64, 256), bf, chip), _sds((b, kk, 64, 256), bf, chip),
        _sds((b, kk, 64, 128), bf, chip), _sds((b, t), jnp.int32, chip),
        _sds((b, kk), jnp.int32, chip)).compile().as_text()
    assert _kernel_calls(text, "window_prefill_attention") == 1


@pytest.mark.parametrize("b,t", [
    pytest.param(4, 512, id="four-pieces-of-512"),
    pytest.param(1, 32, id="a-32-token-tail"),
])
def test_latent_plain_kernel_compiles_at_published_widths(topo, b, t):
    """The plain-form kernel of a dots3 full layer's prompt piece
    (ops/flash_prefill.py `latent_plain_attention`) at the published 128
    heads of 128 | 64 (in 128 lanes) | 128 over a 512-wide latent in bf16,
    pages of 64 in a table of 288: a head's columns of the weights and of
    the queries read at a dynamic lane offset, the int8 mask's block by a
    strided DMA, inside VMEM."""
    from dynamo_tpu.ops.flash_prefill import latent_plain_attention

    chip = SingleDeviceSharding(topo.devices[0])
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = lambda width: _sds((3, 9000, PAGE, 1, width), bf, chip)  # noqa: E731
    text = jax.jit(functools.partial(
        latent_plain_attention, interpret=False)).lower(
        _sds((b, t, 128, 256), bf, chip), _sds((512, 128, 256), bf, chip),
        _sds((b, t, 512), bf, chip), _sds((b, t, 128), bf, chip),
        pool(512), pool(128), _sds((), i32, chip), _sds((b, 288), i32, chip),
        _sds((b,), i32, chip), _sds((b,), i32, chip),
        _sds((b, t, 288 * PAGE), jnp.bool_, chip)).compile().as_text()
    assert _kernel_calls(text, "latent_plain_attention") == 1


@pytest.mark.parametrize("rows,vocab", [
    (64, 152_064),  # qwen2-longgen
    (32, 261_120),  # falconh1-longdoc
    (16, 32_064),  # phi3-chat-closed: not a multiple of the block
])
def test_sample_compiles_with_nothing_sorted_as_wide_as_the_vocabulary(
    topo, rows, vocab
):
    """`engine.sampling.sample` alone at a cell's rows x vocabulary: its
    temporaries stay under two float32 copies of the logits, and no sort or
    top-k of the optimized program takes an operand V wide."""
    import re

    from dynamo_tpu.engine.sampling import sample

    chip = SingleDeviceSharding(topo.devices[0])
    row = lambda dtype: _sds((rows,), dtype, chip)  # noqa: E731
    compiled = jax.jit(sample).lower(
        _sds((rows, vocab), jnp.float32, chip), row(jnp.float32),
        row(jnp.float32), row(jnp.int32), row(jnp.uint32), row(jnp.int32),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * rows * vocab * 4
    text = compiled.as_text()
    # names the program defines with a [rows, vocab] result (tuples too)
    wide = set(re.findall(
        rf"%([\w.\-]+) = [^=]*?\[{rows},{vocab}\][^=]*? [\w\-]+\(", text
    ))
    assert wide, "no [rows, vocab] value found: the HLO text reads otherwise"
    sorts = [
        line for line in text.splitlines()
        if re.search(r" sort\(|custom_call_target=\"(TopK|ApproxTopK)", line)
    ]
    assert sorts, "no sort found: the HLO text reads otherwise"
    for line in sorts:
        operands = re.findall(r"%([\w.\-]+)", line.split(" = ", 1)[1])
        assert not wide & set(operands), line[:300]


def test_engine_refuses_narrow_pages_with_too_few_kv_heads_per_shard():
    """int8 pages under tp=4 leave llama3-1b 2 kv heads per shard, which
    Mosaic cannot DMA: on a TPU the engine says so at construction
    instead of failing inside the first dispatch's compile."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine

    with pytest.raises(ValueError, match="kv heads per shard"):
        JaxEngine(EngineConfig(model="llama3-1b", tp=4, kv_quantize="int8"))


@pytest.mark.parametrize("rows,t,b_pre", [
    pytest.param(32, 1, 0, id="decode-32-rows-8-fused"),
    pytest.param(32, 512, 1, id="mixed-32-rows-beside-a-chunk"),
    pytest.param(32, 512, 4, id="mixed-32-rows-beside-four-chunks"),
    pytest.param(32, 32, 32, id="mixed-32-rows-beside-32-short-prompts"),
])
def test_command_a_plus_step_compiles_at_published_widths(
        topo, caplog, rows, t, b_pre):
    """Whole steps of `command-a-plus-4l-16e` as `cmdaplus-longctx` serves
    it (bf16, 9,000 pages of 64 in the full layer, 36 ring slots of 4,608
    rows of 8 KV heads of 128 in 3 window layers, --max-context 18432): a
    decode row's walk of its ring pages in reach under a bit a ring row and
    of its pages in the full layer (128 query heads over 8 KV heads), a
    prompt piece's banded kernel over its ring and, with a window no
    position reaches, over its pages, the grouped matmuls over the 16 held
    experts, the fused shared experts, the tied head: every pool updated in
    place, the program beside 9.5 GB of weights, 2.4 GB of pages and 2.1 GB
    of rings inside the chip."""
    adapter = get_model("command-a-plus-4l-16e", dtype="bfloat16",
                        attention_impl="pallas")
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(
        lambda: adapter.init_params(jax.random.key(0))))
    kv = _on(chip, jax.eval_shape(
        lambda: adapter.init_kv(9000, PAGE, state_slots=36)))
    assert kv.k.shape == (1, 9000, PAGE, 8, 128)
    assert kv.ring.shape == kv.ring_v.shape == (3, 37, 4608, 8, 128)
    mp = 18432 // PAGE

    def rows_of(b, tt):
        return (
            _sds((b, tt), jnp.int32, chip), _sds((b, tt), jnp.int32, chip),
            _sds((b, tt), jnp.bool_, chip),
            (_sds((b, mp), jnp.int32, chip), _sds((b, 2), jnp.int32, chip)),
        )

    def head(params, hidden):
        logits = adapter.compute_logits(params, hidden[:, -1])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if b_pre:

        def program(params, kv, prompt, decode):
            h_p, h_d, kv = adapter.forward_hidden_mixed(
                params, prompt, decode, kv)
            return head(params, h_d), kv

        args = (rows_of(b_pre, t), rows_of(rows, 1))
    else:

        def program(params, kv, tokens, positions, valid, pt):
            def body(carry, _):
                tokens, positions, kv = carry
                hidden, kv = adapter.forward_hidden(
                    params, tokens, positions, valid, kv, pt)
                ids = head(params, hidden)
                return (ids[:, None], positions + 1, kv), ids

            (_, _, kv), ids = jax.lax.scan(
                body, (tokens, positions, kv), None, length=8)
            return ids, kv

        args = rows_of(rows, t)

    from dynamo_tpu.ops import paged_attention as walk_ops

    walk_ops._told_sub_tiles.clear()
    with caplog.at_level("INFO", logger=walk_ops.__name__):
        compiled = jax.jit(program, donate_argnums=(1,)).lower(
            params, kv, *args).compile()
    # both decode walks (one shape) take 4 pages a DMA block and fold them
    # a page of 512 key columns at a time: said once, at trace time
    told = [r.getMessage() for r in caplog.records
            if "sub-tiles" in r.getMessage()]
    assert len(told) == 1 and (
        "32 rows x 128 / 8 heads walk 4 pages a block in sub-tiles of 512 "
        "key columns" in told[0]), told
    mem = compiled.memory_analysis()
    pools = sum(np.prod(x.shape) * x.dtype.itemsize
                for x in (kv.k, kv.v, kv.ring, kv.ring_v))
    print("command-a-plus compile", rows, t, b_pre, "temp",
          mem.temp_size_in_bytes, "args", mem.argument_size_in_bytes,
          "alias", mem.alias_size_in_bytes, "pools", pools)
    assert mem.alias_size_in_bytes >= pools  # every pool in place
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    text = compiled.as_text()
    calls = {k: _kernel_calls(text, k) for k in (
        "ring_prefill_attention", "paged_prefill_attention",
        "paged_decode_attention", "gather_pages", "paged_kv_write")}
    print(calls)
    # ONE body a kind of layer: the decode walk once in the window layers'
    # body (the ring) and once in the full layer's (the pages); a piece the
    # banded kernel in both, over K and V gathered by page a row at a time
    # (models/llama.py's history kernel at 128 query heads takes the
    # compiler minutes a program: not taken)
    assert calls["paged_decode_attention"] == 2
    assert calls["ring_prefill_attention"] == 2 * int(bool(b_pre))
    assert calls["gather_pages"] == 4 * int(bool(b_pre))
    assert calls["paged_prefill_attention"] == 0
    # no copy of a pool, nor of a layer of the rings
    for copy in ("bf16[3,37,4608,8,128]", "bf16[37,4608,8,128]",
                 "bf16[3,2664,64,8,128]", "bf16[2664,64,8,128]",
                 "bf16[1,9000,64,8,128]", "bf16[9000,64,8,128]",
                 "bf16[3,37,4608,1024]", "bf16[37,4608,1024]"):
        assert not re.search(
            rf"= {re.escape(copy)}[^ ]* copy\(", text), copy


@pytest.mark.parametrize("rows,t,b_pre", [
    pytest.param(32, 1, 0, id="decode-32-rows-8-fused"),
    pytest.param(32, 512, 1, id="mixed-32-rows-beside-a-chunk",
                 marks=pytest.mark.slow),
    pytest.param(32, 512, 4, id="mixed-32-rows-beside-four-chunks",
                 marks=pytest.mark.slow),
    pytest.param(32, 32, 32, id="mixed-32-rows-beside-32-short-prompts",
                 marks=pytest.mark.slow),
])
def test_mimo_v2_step_compiles_at_published_widths(topo, rows, t, b_pre):
    """Whole steps of `mimo-v2.5-7l-16e` as `mimo25-longctx` serves it
    (bf16, 9,000 pages of 64 in 2 full layers of 4 KV heads, 36 ring slots
    of 640 rows of 8 KV heads in 5 window layers, keys 192 beside values
    128 in lane parts, --max-context 18432): a decode row's walk of the 3
    ring pages in reach under a bit a ring row and of its pages in the full
    layers, both over pools in parts, the sink merged outside; a prompt
    piece's banded kernel under the sink over its ring and, with a window
    no position reaches, over its pages; layer 0's dense MLP, the grouped
    matmuls over the 16 held experts, the untied head: every pool updated
    in place and NONE padded (the argument bytes are the pools' own), the
    program beside 6.9 GB of weights, 2.9 GB of pages and 0.6 GB of rings
    inside the chip. The decode case is tier-1's; the mixed ones run on
    demand (`-m slow`)."""
    adapter = get_model("mimo-v2.5-7l-16e", dtype="bfloat16",
                        attention_impl="pallas")
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(
        lambda: adapter.init_params(jax.random.key(0))))
    kv = _on(chip, jax.eval_shape(
        lambda: adapter.init_kv(9000, PAGE, state_slots=36)))
    assert kv.k.shape == (3 * 2, 9000, PAGE, 2, 128)
    assert kv.v.shape == (2 * 2, 9000, PAGE, 2, 128)
    assert kv.ring.shape == (3 * 5, 37, 640, 4, 128)
    assert kv.ring_v.shape == (2 * 5, 37, 640, 4, 128)
    mp = 18432 // PAGE

    def rows_of(b, tt):
        return (
            _sds((b, tt), jnp.int32, chip), _sds((b, tt), jnp.int32, chip),
            _sds((b, tt), jnp.bool_, chip),
            (_sds((b, mp), jnp.int32, chip), _sds((b, 2), jnp.int32, chip)),
        )

    def head(params, hidden):
        logits = adapter.compute_logits(params, hidden[:, -1])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if b_pre:

        def program(params, kv, prompt, decode):
            h_p, h_d, kv = adapter.forward_hidden_mixed(
                params, prompt, decode, kv)
            return head(params, h_d), kv

        args = (rows_of(b_pre, t), rows_of(rows, 1))
    else:

        def program(params, kv, tokens, positions, valid, pt):
            def body(carry, _):
                tokens, positions, kv = carry
                hidden, kv = adapter.forward_hidden(
                    params, tokens, positions, valid, kv, pt)
                ids = head(params, hidden)
                return (ids[:, None], positions + 1, kv), ids

            (_, _, kv), ids = jax.lax.scan(
                body, (tokens, positions, kv), None, length=8)
            return ids, kv

        args = rows_of(rows, t)

    compiled = jax.jit(program, donate_argnums=(1,)).lower(
        params, kv, *args).compile()
    mem = compiled.memory_analysis()
    pools = sum(np.prod(x.shape) * x.dtype.itemsize
                for x in (kv.k, kv.v, kv.ring, kv.ring_v))
    weights = sum(np.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    print("mimo-v2.5 compile", rows, t, b_pre, "temp",
          mem.temp_size_in_bytes, "args", mem.argument_size_in_bytes,
          "alias", mem.alias_size_in_bytes, "pools", pools)
    # a full layer's page is 64 x 2,560 B and a ring row 5,120 B
    assert pools == 2 * 9000 * PAGE * 2560 + 5 * 37 * 640 * 5120
    assert mem.alias_size_in_bytes >= pools  # every pool in place
    # nothing padded: the arguments are the weights, the pools, the rows
    assert mem.argument_size_in_bytes < weights + pools + (8 << 20)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    text = compiled.as_text()
    calls = {k: _kernel_calls(text, k) for k in (
        "ring_prefill_attention", "paged_prefill_attention",
        "paged_decode_attention", "gather_pages", "paged_kv_write")}
    print(calls)
    # ONE body a kind of layer: the decode walk once in the window layers'
    # body (the ring) and once in the full layers' (the pages); a piece the
    # banded kernel in both, over K and V gathered by page a PART at a time
    assert calls["paged_decode_attention"] == 2
    assert calls["ring_prefill_attention"] == 2 * int(bool(b_pre))
    assert calls["gather_pages"] == 2 * 5 * int(bool(b_pre))
    assert calls["paged_prefill_attention"] == 0
    # no copy of a pool, nor of a layer of one
    for copy in ("bf16[15,37,640,4,128]", "bf16[10,37,640,4,128]",
                 "bf16[37,640,4,128]", "bf16[15,370,64,4,128]",
                 "bf16[10,370,64,4,128]", "bf16[370,64,4,128]",
                 "bf16[6,9000,64,2,128]", "bf16[4,9000,64,2,128]",
                 "bf16[9000,64,2,128]", "bf16[6,9000,128,128]",
                 "bf16[15,370,256,128]"):
        assert not re.search(
            rf"= {re.escape(copy)}[^ ]* copy\(", text), copy


def test_gqa_walk_and_ring_kernel_are_what_they_were_before_the_wide_key():
    """PR 56 widens the decode walk by `parts` (a key wider than its value
    in lane parts), `_footprint` / `_block_pages` by `dv`, the banded ring
    kernel by a value width, a sink and the skipping of turns a short
    window cannot reach, and the page writer by a V of other layers than K.
    With the defaults (one part, `dv == dk`, no sink, a window longer than
    the piece) the traced programs are what they were on PR 55's tree, read
    here from the jaxprs at `cmdaplus-longctx`'s served widths: the same
    operands, scratch and primitive counts, and NO semaphore, scratch or
    operand more."""
    from dynamo_tpu.ops import paged_attention as walk_ops
    from dynamo_tpu.ops.flash_prefill import ring_prefill_attention

    S = jax.ShapeDtypeStruct
    b, hq, hkv, mp = 32, 128, 8, 65
    ring = S((3, 2664, 64, hkv, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, pt, hist, bits: paged_decode_attention(
            q, k, v, jnp.int32(1), pt, hist, scale_dim=128, token_bits=bits,
            vmem_budget=16 << 20, interpret=False)
    )(S((b, hq, 128), jnp.bfloat16), ring, ring, S((b, mp), jnp.int32),
      S((b,), jnp.int32), S((b, mp * 64), jnp.bool_))
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    shapes = [str(v.aval) for v in call.invars]
    assert shapes[5:] == [
        "bfloat16[32,128,128]", "bfloat16[3,2664,512,128]",
        "bfloat16[3,2664,512,128]", f"int32[32,{68 * 512}]"]
    body = call.params["jaxpr"]
    scratch = [str(v.aval) for v in
               body.invars[-grid.num_scratch_operands:]]
    assert scratch == ["Ref<vmem>{bfloat16[2,2048,128]}"] * 2 + [
        "Ref<semaphore_mem>{dma_sem[2,2]}"]
    seen = _primitives(body, {})
    # four one-page sub-tiles a block: a score and a value product each
    assert seen["dot_general"] == seen["dot:bfloat16/bfloat16"] == 8
    assert (seen["dma_start"], seen["dma_wait"]) == (4, 2)
    # the block rule reads the shapes as it did: `dv` None is `dv == d`
    for args in ((32, 128, 128, 64, 8, 2, False, 16 << 20),
                 (64, 28, 128, 64, 4, 1, True, None)):
        assert walk_ops._block_pages(*args) == walk_ops._block_pages(
            *args, 0, 128)
        pb = walk_ops._block_pages(*args)
        assert walk_ops._footprint(pb, *args[:7]) == walk_ops._footprint(
            pb, *args[:7], 0, 128)
    assert walk_ops._block_pages(32, 128, 128, 64, 8, 2, False, 16 << 20) == 4
    # the wide walk of this PR: 6 pages of 2 pair-heads a block in the full
    # layers, 3 of 4 in the ring, one sub-tile each
    assert walk_ops._block_pages(
        32, 64, 384, 64, 2, 2, False, 16 << 20, 0, 256) == 6
    assert walk_ops._block_pages(
        32, 64, 384, 64, 4, 2, False, 16 << 20, 0, 256) == 3
    # the banded kernel over a 4,608-row ring under a window of 4,096: eight
    # operands, three scratch buffers, nothing started before the first turn
    r = 4608
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, rk, rv, qp, rp, cp: ring_prefill_attention(
            q, k, v, rk, rv, qp, rp, cp, window=4096, interpret=False)
    )(S((1, 512, hq, 128), jnp.bfloat16), S((1, 512, hkv, 128), jnp.bfloat16),
      S((1, 512, hkv, 128), jnp.bfloat16), S((1, hkv, r, 128), jnp.bfloat16),
      S((1, hkv, r, 128), jnp.bfloat16), S((1, 512), jnp.int32),
      S((1, r), jnp.int32), S((1, 512), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "ring_prefill_attention"
    grid = call.params["grid_mapping"]
    assert grid.grid == (1, 8, 4) and grid.num_index_operands == 1
    assert len(call.invars) == 1 + 8 and grid.num_scratch_operands == 3
    seen = _primitives(call.params["jaxpr"], {})
    # four own tiles and the ring's turn: a score and a value product each
    assert seen["dot_general"] == 2 * (4 + 1)
    assert seen["cond"] == 3 + 1  # own tiles 1-3 and the ring's turn
