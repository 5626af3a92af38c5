"""The deployment the serve cells run: the program's ``LLMServer`` with the
benchmark's own set-up and probes around it, all called through the handle as
``kv_stats`` is.

It adds nothing to the served path but a ``TraceAnnotation`` around each
``engine.step`` and two clock reads: weights come from the seed in one jitted
call of the cell's architecture (``models/<model_type>.py``), ``warm`` has
every shape the engine's buckets allow run once, and ``arm_trace`` makes the
engine's own thread start the profiler before a step and stop it after one, a
few seconds later — only this process can trace the chip. It names no model.
"""

from __future__ import annotations

import importlib
import itertools
import time
from typing import Any, Dict, Optional

from ray_tpu.serve import llm

from benchmark.manifest import published_keys
from benchmark.tracing import SubWindowTrace

ANNOTATION = "bench.engine_step"


class BenchLLMServer(llm.LLMServer):
    def __init__(
        self, architecture: str, reference: str, config: Dict[str, Any], seed: int = 0,
        **engine,
    ):
        """``architecture`` and ``reference`` are module names (``Cell``'s),
        ``config`` the configuration's file, ``engine`` its engine sizes."""
        self._architecture = importlib.import_module(architecture)
        self._reference = importlib.import_module(reference)
        self._config = config
        cfg = self._architecture.program_config(published_keys(config))
        t0 = time.perf_counter()
        # the engine serves this very tree: the reference reads it from here
        self._weights = self._architecture.seeded_params(cfg, seed)
        self._weights_s = time.perf_counter() - t0
        super().__init__(cfg, params=self._weights, seed=seed, **engine)
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
        """Have every shape the buckets allow run once, on zeros made on the
        device, and size the largest one as the compiler sees it.

        That is the engine's to do: where it has a ``warm()`` of its own, that
        is called, and returns ``shapes`` (how many), ``warm_s`` and
        ``compiled`` (as below, or None). ``LLMEngine`` has none yet (a program
        change, which a ``benchmark`` PR may not make: PERF.md, section 7), so
        until then the arguments of its jitted ``extend`` are built here, which
        pins ``extend``'s signature (a contiguous K/V pair, one K/V head per
        query head) and names two private attributes of the engine. The loop
        stays in this method's body: moved into a function of its own, the same
        twelve first calls took 6.2 s where they take 4.2 s here (PERF.md, PR 26)."""
        import jax
        import jax.numpy as jnp

        eng, cfg = self._engine, self._engine.cfg
        own = getattr(eng, "warm", None)
        if own is not None:
            report = own()
        else:
            t0 = time.perf_counter()
            shapes = list(itertools.product(
                eng.lane_buckets, [1] + eng.prefill_token_buckets, eng.cache_buckets
            ))

            def args(b, tc, cap):
                kv = jnp.zeros(
                    (cfg.num_layers, b, cap, cfg.num_heads, cfg.head_dim), cfg.dtype
                )
                return (
                    eng._params, jnp.zeros((b, tc), jnp.int32), jnp.zeros((b,), jnp.int32),
                    kv, kv,
                )

            for shape in shapes:
                jax.block_until_ready(eng._extend(*args(*shape)))
            warm_s = time.perf_counter() - t0
            largest = max(shapes, key=lambda s: (s[0] * s[2], s[1]))
            memory = eng._extend.lower(*args(*largest)).compile().memory_analysis()
            report = {
                "shapes": len(shapes), "warm_s": warm_s,
                "compiled": None if memory is None else {
                    "shape": list(largest),
                    "argument_bytes": memory.argument_size_in_bytes,
                    "temp_bytes": memory.temp_size_in_bytes,
                    "output_bytes": memory.output_size_in_bytes,
                    "alias_bytes": memory.alias_size_in_bytes,
                },
            }
        return {
            **report, "weights_s": self._weights_s,
            "model": self._architecture.describe(cfg), "vocab_size": cfg.vocab_size,
        }

    def reference_logits(self, tokens, last: int):
        """Float32 logits [last, vocab] of the configuration's plain reference
        for the last positions of ``tokens``, from the weights being served."""
        import numpy as np

        return np.asarray(
            self._reference.program_logits(self._weights, tokens, self._config, last)
        )

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
