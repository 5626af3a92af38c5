"""GPT-J: the system against the benchmark's plain reference, on seeded random
weights at a small size on the CPU: the train path (logits, loss, gradients)
and the server (prefill, then decode through the paged cache). float32
throughout; the weights are scaled up so that logits are of order 1 to 10, and
the tolerance of 1e-3 is some float32 roundings (2^-23 each) of such values
summed over 64 to 256 terms through two layers (3e-4 was seen). Computing in
bfloat16, at 2^-8 a rounding, would miss it by an order of magnitude. And
the operations ``models/gptj.py`` counts for one train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_helpers
from benchmark.models import gptj
from benchmark.reference import gptj_reference as ref

TINY = bench_helpers.tiny("gptj")
MODEL = TINY["model"]
CONFIG = {**MODEL, "reference": TINY["reference"]}      # as a configuration's file holds them
ENGINE = next(c["engine"] for c in TINY["cells"] if "engine" in c)
# the program's LayerNorm epsilon (the source says 1e-5)
PROGRAM_EPS = TINY["reference"]["program_layer_norm_epsilon"]
TOL = 1e-3


@pytest.fixture(scope="module")
def weights():
    cfg = gptj.program_config(MODEL)
    program = jax.tree.map(
        # the init's 0.02 would leave every logit near 0: make the weights matter
        lambda a: a * 8.0 if a.ndim > 1 else a + 0.1,
        gptj.seeded_params(cfg, seed=3),
    )
    return cfg, program, ref.from_program_params(program, MODEL)


def test_train_path_logits_loss_and_gradients_match_the_reference(weights):
    from ray_tpu.models import gpt

    cfg, program, reference = weights
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 48), 0, cfg.vocab_size)
    got = gpt.GPT(cfg).apply({"params": program}, tokens)
    want = ref.forward(reference, tokens, MODEL, eps=PROGRAM_EPS)
    assert float(jnp.abs(want).max()) > 1.0                # not all but zero
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    # the published epsilon moves the result by more than the tolerance allows
    # to pass by accident, and by little: the departure is small and real
    published = ref.forward(reference, tokens, MODEL)
    assert 0 < float(jnp.abs(published - want).max()) < 0.05

    def program_loss(p):
        hidden, kernel, bias = gpt.GPT(cfg, return_hidden=True).apply({"params": p}, tokens)
        return gpt.blockwise_next_token_loss(hidden, kernel, bias, tokens, chunk=16)

    loss, grads = jax.value_and_grad(program_loss)(program)
    want_loss, want_grads = jax.value_and_grad(ref.loss)(
        reference, tokens, MODEL, PROGRAM_EPS
    )
    assert float(loss) == pytest.approx(float(want_loss), abs=TOL)
    np.testing.assert_allclose(
        grads["lm_head"]["kernel"], want_grads["head"], atol=TOL, rtol=1e-2
    )
    np.testing.assert_allclose(
        grads["blocks"]["layers"]["mlp"]["wi"]["kernel"][1], want_grads["layers"][1]["wi"],
        atol=TOL, rtol=1e-2,
    )


def test_prefill_then_decode_through_the_cache_matches_the_full_forward(weights):
    from ray_tpu.serve import llm

    cfg, program, reference = weights
    server = llm.LLMServer(cfg, params=program, **ENGINE)
    prompt = [int(t) for t in np.random.default_rng(1).integers(0, cfg.vocab_size, size=50)]
    out = server({"prompt": prompt, "max_new_tokens": 6, "return_logits": True})
    # teacher-force the server's own tokens through the reference: position
    # len(prompt) - 1 + i predicts generated token i
    fed = jnp.asarray([prompt + out["tokens"][:-1]])
    want = ref.forward(reference, fed, MODEL, eps=PROGRAM_EPS)[0, len(prompt) - 1:]
    assert out["logits"].shape == want.shape == (6, cfg.vocab_size)
    np.testing.assert_allclose(out["logits"], want, atol=TOL, rtol=TOL)
    assert out["tokens"] == [int(t) for t in want.argmax(-1)]


def test_the_reference_a_layer_at_a_time_is_the_reference_and_misses_no_layer(weights):
    """What the runs on the chip call: the program's own weights, one layer at
    a time. Equal to the plain forward; and a model that skips its last layer
    is far outside what a run allows."""
    from benchmark import yardstick

    cfg, program, reference = weights
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, cfg.vocab_size)
    want = ref.forward(reference, tokens[:1], MODEL, eps=PROGRAM_EPS)[0, -3:]
    got = ref.program_logits(program, tokens[0], CONFIG, 3)
    assert yardstick.logits_error(got, want) < 1e-5
    assert ref.program_loss(program, tokens, CONFIG) == pytest.approx(
        float(ref.loss(reference, tokens, MODEL, PROGRAM_EPS)), abs=1e-5
    )
    shallow = {**CONFIG, "n_layer": MODEL["n_layer"] - 1}
    skipped = ref.program_logits(program, tokens[0], shallow, 3)
    assert yardstick.logits_error(skipped, want) > 0.1
    assert yardstick.logits_error(want + 1.0, want) == pytest.approx(1.0 / float(want.std()))


def test_flops_count_what_causal_attention_requires():
    model = {"n_embd": 4096, "n_head": 16, "n_inner": None, "n_layer": 6,
             "vocab_size": 50400}
    assert gptj.matmul_params(model) == 6 * (4 * 4096**2 + 2 * 4096 * 16384) + 4096 * 50400
    flops = gptj.train_step_flops(model, 4, 2048)
    assert flops == pytest.approx(72.0e12, rel=0.01)
    # about half of the full square the program's own count takes
    attention = flops - 6.0 * gptj.matmul_params(model) * 8192
    assert attention == pytest.approx(12 * 6 * 4 * 4096 * 2048 * 2048 / 2, rel=1e-3)
