"""Canonical LLM serving components for the SDK graphs.

Reference parity: examples/llm/components/{frontend,worker,prefill_worker}
— the deployment everyone starts from (examples/llm/graphs/agg.py etc.),
rebuilt on this framework's runtime: the Frontend serves OpenAI HTTP and
watches MODEL_ROOT so workers attach dynamically; Worker wraps the JAX
engine worker (aggregated or disaggregated decode); PrefillWorkerService
drains the shared prefill queue.

Config keys (YAML per service, see configs/):
  Frontend:   port
  Worker:     model, engine (jax|echo|mock), router-mode, page-size,
              num-pages, max-context, dtype, disagg, max-local-prefill,
              prefill-chunk, prefill-budget, prefill-policy (fixed|adaptive),
              prefill-budget-max, max-seqs, decode-steps, spec-ngram,
              spec-draft, spec-draft-tokens, spec-draft-checkpoint,
              quantize, host-kv-bytes, disk-kv-bytes, disk-kv-dir,
              dp, tp, sp, ep
  PrefillWorkerService: model + the same engine keys as Worker
"""

from __future__ import annotations

from typing import Optional

from dynamo_tpu.sdk import depends, service


def _engine_config(cfg: dict):
    from dynamo_tpu.engine import EngineConfig

    page_size = int(cfg.get("page-size", 64))
    max_context = int(cfg.get("max-context", 4096))
    return EngineConfig(
        model=cfg.get("model", "tiny"),
        num_pages=int(cfg.get("num-pages", 2048)),
        page_size=page_size,
        max_pages_per_seq=max(1, max_context // page_size),
        prefill_chunk=int(cfg.get("prefill-chunk", 512)),
        max_seqs=int(cfg.get("max-seqs", 64)),
        dtype=cfg.get("dtype", "bfloat16"),
        decode_steps=int(cfg.get("decode-steps", 8)),
        spec_ngram=int(cfg.get("spec-ngram", 0)),
        spec_draft_model=cfg.get("spec-draft"),
        spec_draft_tokens=int(cfg.get("spec-draft-tokens", 4)),
        spec_draft_checkpoint=cfg.get("spec-draft-checkpoint"),
        quantize=cfg.get("quantize"),
        prefill_token_budget=(
            int(cfg["prefill-budget"])
            if cfg.get("prefill-budget") is not None
            else None
        ),
        prefill_budget_policy=cfg.get("prefill-policy", "fixed"),
        prefill_budget_max=(
            int(cfg["prefill-budget-max"])
            if cfg.get("prefill-budget-max") is not None
            else None
        ),
        host_kv_cache_bytes=int(cfg.get("host-kv-bytes", 0)),
        disk_kv_cache_bytes=int(cfg.get("disk-kv-bytes", 0)),
        disk_kv_cache_dir=cfg.get("disk-kv-dir"),
        dp=int(cfg.get("dp", 1)),
        tp=int(cfg.get("tp", 1)),
        sp=int(cfg.get("sp", 1)),
        ep=int(cfg.get("ep", 1)),
    )


def _card(cfg: dict):
    from dynamo_tpu.model_card import ModelDeploymentCard

    tokenizer = {"kind": "byte"}
    if cfg.get("tokenizer"):
        tokenizer = {"kind": "hf", "path": cfg["tokenizer"]}
    return ModelDeploymentCard(
        name=cfg.get("model", "tiny"),
        tokenizer=tokenizer,
        context_length=int(cfg.get("max-context", 4096)),
        kv_page_size=int(cfg.get("page-size", 64)),
    )


@service
class Worker:
    """Engine worker: serves `generate`/`embed`/`flush`, publishes KV
    events + load metrics, optionally decodes with remote prefill."""

    def __init__(self):
        self._worker = None

    async def setup(self):
        from dynamo_tpu.worker import Worker as EngineWorker

        cfg = self.config
        disagg_config = None
        if cfg.get("disagg"):
            from dynamo_tpu.disagg import DisaggConfig

            disagg_config = DisaggConfig(
                max_local_prefill_length=int(
                    cfg.get("max-local-prefill", 512)
                )
            )
        self._worker = EngineWorker(
            self.runtime,
            _card(cfg),
            engine_config=(
                _engine_config(cfg)
                if cfg.get("engine", "jax") == "jax"
                else None
            ),
            engine_kind=cfg.get("engine", "jax"),
            router_mode=cfg.get("router-mode", "round_robin"),
            enable_disagg=bool(cfg.get("disagg")),
            disagg_config=disagg_config,
            checkpoint_path=cfg.get("checkpoint"),
        )
        await self._worker.start()

    async def teardown(self):
        if self._worker is not None:
            await self._worker.stop()


@service
class PrefillWorkerService:
    """Stateless prefill worker: pulls RemotePrefillRequests off the shared
    queue, runs the prefill pass, pushes KV pages to the decode worker."""

    def __init__(self):
        self._worker = None

    async def setup(self):
        from dynamo_tpu.disagg.prefill_worker import PrefillWorker

        self._worker = PrefillWorker(
            self.runtime,
            _engine_config(self.config),
            checkpoint_path=self.config.get("checkpoint"),
        )
        await self._worker.start()

    async def teardown(self):
        if self._worker is not None:
            await self._worker.stop()


class _FrontendBase:
    def __init__(self):
        self.http: Optional[object] = None
        self._watcher = None
        self.port = None

    def _make_manager(self):
        """Hook for deployments that wrap the manager (e.g. the multimodal
        frontend attaches an image encoder to every pipeline)."""
        from dynamo_tpu.frontend import ModelManager

        return ModelManager()

    async def setup(self):
        from dynamo_tpu.frontend import HttpService
        from dynamo_tpu.frontend.service import ModelWatcher

        manager = self._make_manager()
        self.http = HttpService(
            manager,
            host=self.config.get("host", "0.0.0.0"),
            port=int(self.config.get("port", 8080)),
        )
        await self.http.start()
        self.port = self.http.port
        self._watcher = ModelWatcher(self.runtime, manager)
        await self._watcher.start()

    async def teardown(self):
        if self._watcher is not None:
            await self._watcher.stop()
        if self.http is not None:
            await self.http.stop()


@service
class Frontend(_FrontendBase):
    """OpenAI-compatible HTTP frontend; models attach via MODEL_ROOT watch."""

    worker = depends(Worker)


@service
class DisaggFrontend(_FrontendBase):
    """Frontend for the disaggregated graphs (decode + prefill workers)."""

    worker = depends(Worker)
    prefill = depends(PrefillWorkerService)
