"""The deployment the serve cells run: the program's ``LLMServer`` with the
benchmark's own set-up and probes around it, all called through the handle as
``kv_stats`` is.

It adds nothing to the served path but a ``TraceAnnotation`` around each
``engine.step`` and two clock reads: weights come from the seed in one jitted
call, ``warm`` runs every shape the engine's buckets allow once, and
``arm_trace`` makes the engine's own thread start the profiler before a step
and stop it after one, a few seconds later — only this process can trace the
chip.
"""

from __future__ import annotations

import importlib
import itertools
import time
from typing import Any, Dict, Optional

from ray_tpu.serve import llm

from benchmark import model as model_mod
from benchmark.tracing import SubWindowTrace

ANNOTATION = "bench.engine_step"


class BenchLLMServer(llm.LLMServer):
    def __init__(self, model: Dict[str, Any], seed: int = 0, **engine):
        cfg = model_mod.gpt_config(model)
        self._model = model
        t0 = time.perf_counter()
        params = model_mod.seeded_params(cfg, seed)
        self._weights_s = time.perf_counter() - t0
        super().__init__(cfg, params=params, seed=seed, **engine)
        self._trace: Optional[SubWindowTrace] = None
        self._armed: Optional[Dict[str, float]] = None
        self._probe: Dict[str, Any] = {}
        self._inner_step = self._engine.step
        self._engine.step = self._step

    # -- around every engine step (the engine's own thread) -----------------

    def _step(self, seqs) -> None:
        armed, trace = self._armed, self._trace
        if armed is not None and trace is None and time.time() >= armed["start_at"]:
            trace = self._trace = SubWindowTrace(ANNOTATION)
            self._probe = {
                "steps0": self._engine.steps, "decode0": self._engine.decode_tokens,
                "in_step_s": 0.0,
            }
            trace.start()
        if trace is None or not trace.running:
            return self._inner_step(seqs)
        t0 = time.perf_counter()
        try:
            with trace.unit():
                return self._inner_step(seqs)
        finally:
            self._probe["in_step_s"] += time.perf_counter() - t0
            if time.time() - trace.started_at >= armed["seconds"]:
                self._stop_trace()

    def _stop_trace(self) -> None:
        self._probe.update(
            steps=self._engine.steps - self._probe["steps0"],
            decode_tokens=self._engine.decode_tokens - self._probe["decode0"],
        )
        self._trace.stop()

    # -- called through the handle -----------------------------------------

    def warm(self) -> Dict[str, Any]:
        """Run every shape the buckets allow once, on zeros made on the
        device, and size the largest one as the compiler sees it."""
        import jax
        import jax.numpy as jnp

        eng, cfg = self._engine, self._engine.cfg
        t0 = time.perf_counter()
        shapes = list(itertools.product(
            eng.lane_buckets, [1] + eng.prefill_token_buckets, eng.cache_buckets
        ))

        def args(b, tc, cap):
            kv = jnp.zeros((cfg.num_layers, b, cap, cfg.num_heads, cfg.head_dim), cfg.dtype)
            return (
                eng._params, jnp.zeros((b, tc), jnp.int32), jnp.zeros((b,), jnp.int32),
                kv, kv,
            )

        for shape in shapes:
            jax.block_until_ready(eng._extend(*args(*shape)))
        warm_s = time.perf_counter() - t0
        largest = max(shapes, key=lambda s: (s[0] * s[2], s[1]))
        memory = eng._extend.lower(*args(*largest)).compile().memory_analysis()
        compiled = None if memory is None else {
            "shape": list(largest),
            "argument_bytes": memory.argument_size_in_bytes,
            "temp_bytes": memory.temp_size_in_bytes,
            "output_bytes": memory.output_size_in_bytes,
            "alias_bytes": memory.alias_size_in_bytes,
        }
        return {
            "shapes": len(shapes), "warm_s": warm_s, "weights_s": self._weights_s,
            "model": model_mod.describe(cfg), "compiled": compiled,
        }

    def reference_logits(self, reference: Dict[str, Any], tokens, last: int):
        """Float32 logits [last, vocab] of the configuration's plain reference
        for the last positions of ``tokens``, from the weights being served."""
        import numpy as np

        module = importlib.import_module(f"benchmark.reference.{reference['module']}")
        return np.asarray(module.program_logits(
            self._engine._params, tokens, self._model,
            reference["program_layer_norm_epsilon"], last,
        ))

    def arm_trace(self, start_at: float, seconds: float) -> bool:
        """From wall-clock ``start_at`` on, the next engine step starts the
        profiler; the first step to end ``seconds`` later stops it."""
        self._armed = {"start_at": float(start_at), "seconds": float(seconds)}
        return True

    def trace_result(self) -> Optional[Dict[str, Any]]:
        """The reduced trace of the armed sub-window with the engine's own
        counters over it; None where no step ran in it."""
        trace = self._trace
        if trace is None:
            return None
        if trace.running:                       # no step ended late enough
            self._stop_trace()
        reduced = trace.result() or {}       # no device plane: the counters alone
        reduced["engine"] = {
            k: self._probe[k] for k in ("steps", "decode_tokens", "in_step_s")
        }
        return reduced
