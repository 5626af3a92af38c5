"""What the chip's compiler says about the serve path, asked without a chip:
every configuration's ``extend`` at its largest shapes, the paging programs,
the programs' names and their count.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
described (not attached) ``v5e:2x2``: it refuses what the chip would refuse
— a kernel whose tiles do not fit, a Mosaic kernel under a mesh without a
``shard_map``, a program larger than the device's memory. Nothing runs, so
these say nothing about results or speed; that is ``chip_smoke.py``'s job.

Code that asks ``jax.devices()`` sees the CPU here and would take its XLA
path, so the tests answer for the package's one probe (``built_for_tpu``,
``tests/conftest.py``).
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile_helpers import (  # noqa: F401 — shaped and v5e are fixtures
    HBM_BYTES, _device_bytes, _gptj, shaped, v5e)
from ray_tpu.models import (
    cohere2_moe, glm_moe_dsa, gpt, granitemoehybrid, keye_vl2, kimi_k2, layers, longcat_flash,
    mimo_v2_flash, minicpm_sala, qwen3_next)
from ray_tpu.serve import llm


def _all_rows_bytes(cfg, lanes, tc):
    """The float32 logits and hidden rows of every fed position, which a chunk's
    ``extend`` handed back until PR 57. The compiler kept a chunk's temporaries in
    that buffer while it was free and counted them as output, so a bound on a
    prefill program's temporaries taken from the all-rows form has these in it."""
    return 4 * lanes * tc * (cfg.vocab_size + cfg.embed_dim) if tc > 1 else 0


def _holds_no_more_than_stated(memory, stated):
    """Temporaries and outputs together against a configuration file's figures (the
    all-rows form's: ``_all_rows_bytes``), the temporaries with the 5 % a compiler's
    release may add."""
    return (memory.temp_size_in_bytes + memory.output_size_in_bytes
            <= stated["temp"] * 1.05 + stated["output"])


def _extend_at(cfg, shaped, lanes, tc, cap):
    """``extend`` as a step calls it (tokens and lengths read from the call's
    operand buffer), compiled for ``lanes`` x ``tc`` tokens over a ``cap`` cache."""
    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype),
        jax.eval_shape(
            lambda: gpt.unboxed_params(
                gpt.GPT(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
            )
        ),
    )
    cache = shaped((cfg.num_layers, lanes, cap, cfg.num_heads, cfg.head_dim), cfg.dtype)
    operands = shaped((lanes, llm._operand_width(tc, cap // 16)), jnp.int32)
    return llm._operand_extend(gpt.make_extend_fn(cfg)).lower(
        llm._extend_name(lanes, tc, cap), params, operands, shaped((lanes,), jnp.int32), cache, cache, tc=tc).compile()



def _caches_as_the_engine_hands_them(cfg, engine, shaped, b, tc, cap):
    """What ``_extend_call`` is handed for the caches, and its statics: a decode call of
    a model whose ``extend`` reads pages (``llm.reads_pages``) the pool's arenas
    themselves and how many pages a lane's table has; any other call the padded caches."""
    cached = _cache_layers(cfg)
    if tc == 1 and llm.reads_pages(cfg.make_extend_fn()):
        return [
            shaped((cached, engine["num_blocks"], engine["block_size"]) + tuple(each), cfg.dtype)
            for each in cfg.cache_arrays], dict(tc=tc, pages=cap // engine["block_size"])
    return [
        shaped((cached, b, cap) + tuple(each), cfg.dtype) for each in cfg.cache_arrays], dict(tc=tc)


def _cache_layers(cfg):
    """The layers in which a token is cached: all of them where the configuration names none."""
    return getattr(cfg, "cache_layers", cfg.num_layers)


def _attends_through_the_table(text, cfg, engine, sites):
    """A compiled decode program that reads pages: its attend is ``paged_attention``
    under ``extend.attention`` at ``sites`` call sites, and nothing in its text but a
    parameter, a bitcast of one or a loop's hand-over has the shape of a K/V arena or
    of a layer's slab of one: no copy, no slice, no re-layout of either."""
    kernels = [
        line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    attends = [k for k in kernels if "/paged_attention" in k]
    assert len(attends) == sites and all("/extend.attention/paged_attention" in k for k in attends)
    assert not any("/masked_attention/" in k for k in kernels)
    of_an_arena = re.compile(
        r" = bf16\[(%d,)?%d,%d,[\d,]+\]\S* (\w[\w-]*)\(" % (
            _cache_layers(cfg), engine["num_blocks"], engine["block_size"]))
    made = {m[2] for m in map(of_an_arena.search, text.splitlines()) if m}
    assert made and made <= {"parameter", "bitcast", "get-tuple-element"}, made
    return [k for k in kernels if k not in attends]


@pytest.mark.parametrize("lanes,tc", [(4, 1), (1, 128)], ids=["decode", "prefill"])
def test_gptj_full_depth_extend_compiles(shaped, lanes, tc):
    """The server's step at full depth 28 in bf16, over a 1024-token cache:
    the weights alone are 11.3 GiB of the chip's 15.75."""
    assert _device_bytes(_extend_at(_gptj(28), shaped, lanes, tc, 1024)) < HBM_BYTES


def _experts_kernels_and_a_chunks_attend(text, cfg, lanes, tc, cap):
    """The kernels of a compiled ``extend`` with an expert layer: the grouped
    matmuls, and for a chunk (``tc`` > 1) one more, its attend, straight under
    the scope the readers count, with no array of the dense form's shapes left
    (32 queries' float32 scores over the cache, and their weights); a decode
    call holds the experts' alone."""
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    experts = [line for line in kernels if "extend.moe.experts" in line]
    assert len(experts) >= 2                                     # the kernel is there
    if tc == 1:
        assert kernels == experts
        return
    (attend,) = [line for line in kernels if line not in experts]
    assert "/extend.attention/masked_attention/" in attend
    groups = cfg.num_heads // cfg.kv_heads
    for scores in ("f32", "bf16"):
        assert f"{scores}[{lanes},{cfg.kv_heads},{groups},{layers.QUERY_BLOCK},{cap}]" not in text


@pytest.mark.parametrize(
    "lanes,tc,cap,parent_temp",
    [(8, 1, 8192, 404638208), (1, 256, 8192, 270579712), (2, 256, 8192, 631700992)],
    ids=["decode", "prefill", "prefill-two-lanes"])
def test_command_a_plus_share_extend_compiles_and_copies_no_expert(
    shaped, lanes, tc, cap, parent_temp, built_for_tpu
):
    """The served share of Command A+ at its published widths (one period, 16
    of 128 experts, an eighth of the vocabulary: 9.47 GB of weights) over the
    largest cache bucket: it fits beside a 0.8 GB pool, and its temporaries stay
    under a layer's routed experts (1.6 GB), which a scan that sliced them out
    of the stack copied on every call (``moe.held_experts_ffn``, ``layer``).
    A chunk (the cell's one prefill lane, and two) attends in
    ``masked_attention``; a decode call holds the experts' kernels alone.
    ``parent_temp`` is what the compiler counted at PR 52 for the same shape,
    where a chunk attended densely (compile, PR 53). Since PR 59 a chunk's ``q``
    projection writes by head from its kernel as it lies
    (``ops/attention.laid_out_by_head``): the 134 MB copy of the layer's ``q`` kernel,
    which every chunk made of every layer and which was half of a one-lane chunk's
    temporaries (272,467,456 B at PR 58), is gone; the slice of the stack that the
    product reads is still made."""
    built_for_tpu(True)     # the chip's grouped matmul and attend
    cfg = cohere2_moe.Cohere2MoeConfig(vocab_size=32768, num_layers=4, num_experts=16)
    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype), jax.eval_shape(lambda: cfg.init_params(0)))
    cache = shaped((cfg.num_layers, lanes, cap, cfg.kv_heads, cfg.head_dim), cfg.dtype)
    operands = shaped((lanes, llm._operand_width(256, 8192 // 256)), jnp.int32)
    compiled = llm._operand_extend(cfg.make_extend_fn()).lower(
        llm._extend_name(lanes, tc, cap), params, operands, shaped((8,), jnp.int32),
        cache, cache, tc=tc).compile()
    text = compiled.as_text()
    _experts_kernels_and_a_chunks_attend(text, cfg, lanes, tc, cap)
    memory = compiled.memory_analysis()
    if tc > 1:
        q_kernel = rf"bf16\[1,{cfg.embed_dim},{cfg.num_heads},{cfg.head_dim}\]"
        assert re.search(rf"= {q_kernel}\S* fusion\(", text)             # the slice
        # no copy of it as an instruction of its own (one fused into the product,
        # of a fusion's parameter, re-lays the kernel out as the product reads it)
        assert not re.search(rf"= {q_kernel}\S* (copy|transpose)\(%(?!param_)", text)
        q_kernel_bytes = 2 * cfg.embed_dim * cfg.num_heads * cfg.head_dim
        assert memory.temp_size_in_bytes <= {1: 272467456}.get(lanes, parent_temp) - q_kernel_bytes
    assert 9.4e9 < memory.argument_size_in_bytes < 10.6e9
    assert memory.temp_size_in_bytes < 1.0e9
    # a chunk's bound is the all-rows form's
    assert memory.temp_size_in_bytes <= parent_temp + _all_rows_bytes(cfg, lanes, tc)
    assert _device_bytes(compiled) + 2 * 0.41e9 < HBM_BYTES


def test_gptj_serve_paging_programs_compile_beside_the_weights(shaped):
    """The serve configuration's pool on the chip (320 blocks of 16 tokens, 28
    layers, bf16: 1.17 GB an arena) and the programs around ``extend``, for
    every shape the configuration's buckets allow: no program holds a
    temporary the size of an arena (the compiler's own gather and scatter
    do), the page-back and the clone alias both arenas (the donation took; an
    update that did not would hold a second copy), and at the largest shape (4
    lanes, 128 tokens, 512 cache) weights + pool + pair + outputs stay under
    the chip's limit in each program."""
    cfg, blocks, block = _gptj(28), 320, 16
    lanes, tokens, caches = (1, 4), (1, 32, 128), (256, 512)
    arena = shaped((cfg.num_layers, blocks, block, cfg.num_heads, cfg.head_dim), cfg.dtype)
    arena_bytes = 2 * cfg.num_layers * blocks * block * cfg.num_heads * cfg.head_dim
    programs = llm._paging_programs()
    # a call's operands: one int32 buffer, as wide as the widest buckets ask
    width = llm._operand_width(tokens[-1], caches[-1] // block)

    def pair_bytes(b, cap):
        return 2 * 2 * cfg.num_layers * b * cap * cfg.num_heads * cfg.head_dim

    def gather(b, cap):
        return programs.gather.lower(
            f"gather_{b}x{cap}", (arena, arena), shaped((b, width), jnp.int32), cap // block).compile()

    def page_back(b, tc):
        new = shaped((cfg.num_layers, b, tc, cfg.num_heads, cfg.head_dim), cfg.dtype)
        return programs.page_back.lower(
            f"page_back_{b}x{tc}", (arena, arena), (new, new), shaped((b, width), jnp.int32),
            shaped((b, cfg.vocab_size), jnp.float32), (), lanes[-1],
        ).compile()

    for b in lanes:
        for cap in caches:
            memory = gather(b, cap).memory_analysis()
            assert 0 <= memory.output_size_in_bytes - pair_bytes(b, cap) < 4096
            assert memory.temp_size_in_bytes < 2**20, (b, cap)
        for tc in tokens:
            memory = page_back(b, tc).memory_analysis()
            assert memory.alias_size_in_bytes == 2 * arena_bytes, (b, tc)
            assert memory.temp_size_in_bytes < 2**20, (b, tc)
            # what it returns beside the arenas: the ids, as wide as the widest
            # lane bucket (the next call reads them too); it picks no row
            assert 0 <= memory.output_size_in_bytes - 2 * arena_bytes - 4 * lanes[-1] < 4096
    clone = programs.clone.lower(
        (arena, arena), shaped((), jnp.int32), shaped((), jnp.int32)).compile()
    assert clone.memory_analysis().alias_size_in_bytes == 2 * arena_bytes
    assert clone.memory_analysis().temp_size_in_bytes < 2**20

    b, tc, cap = lanes[-1], tokens[-1], caches[-1]
    extend = _extend_at(cfg, shaped, b, tc, cap)
    weights_bytes = extend.memory_analysis().argument_size_in_bytes - pair_bytes(b, cap)
    assert 12.0e9 < weights_bytes < 12.2e9
    assert weights_bytes + _device_bytes(gather(b, cap)) < HBM_BYTES
    assert _device_bytes(extend) + 2 * arena_bytes < HBM_BYTES
    # the pair may still be alive (extend has been dispatched, not awaited)
    assert weights_bytes + pair_bytes(b, cap) + _device_bytes(page_back(b, tc)) < HBM_BYTES


def _keye_stage():
    """The served cut of Keye-VL-2.0's language model and its engine sizes,
    from the configuration's file."""
    import json

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "keye-vl2-30b-a3b-serve.json")) as f:
        config = json.load(f)
    return keye_vl2.KeyeVL2Config(num_layers=config["num_hidden_layers"]), config


_KEYE_COMPILED = {}


def _keye_extend_at_its_largest(shaped, built_for_tpu, form):
    """``(compiled, b, tc, cap)``: the stage's ``extend`` as a step calls it, in its
    largest decode or prefill shape; compiled once a form for the tests below."""
    built_for_tpu(True)     # the chip's grouped matmul
    cfg, config = _keye_stage()
    engine = config["engine"]
    cap, lanes = engine["cache_buckets"][-1], engine["lane_buckets"][-1]
    b, tc = (lanes, 1) if form == "decode" else (1, engine["prefill_token_buckets"][-1])
    if form not in _KEYE_COMPILED:
        params = jax.tree.map(
            lambda x: shaped(x.shape, x.dtype), jax.eval_shape(lambda: cfg.init_params(0)))
        caches = [
            shaped((cfg.num_layers, b, cap) + tuple(each), cfg.dtype)
            for each in cfg.cache_arrays]
        operands = shaped(
            (b, llm._operand_width(
                engine["prefill_token_buckets"][-1], cap // engine["block_size"])),
            jnp.int32)
        _KEYE_COMPILED[form] = llm._operand_extend(cfg.make_extend_fn()).lower(
            llm._extend_name(b, tc, cap), params, operands,
            shaped((lanes + len(cfg.counters),), jnp.int32), *caches, tc=tc
        ).compile()
    return _KEYE_COMPILED[form], b, tc, cap


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_keye_vl2_stage_extend_compiles_at_its_largest_shapes(shaped, form, built_for_tpu):
    """One pipeline stage of Keye-VL-2.0's language model at its published
    widths (six layers, every expert, the whole vocabulary: 8.75 GB of weights)
    over the largest cache bucket, in both forms of the selection: it fits
    beside the pool and a second call's caches, copies no layer's experts
    (1.2 GB) and holds the memory the configuration's file states."""
    compiled, b, tc, cap = _keye_extend_at_its_largest(shaped, built_for_tpu, form)
    cfg, config = _keye_stage()
    engine, stated = config["engine"], config["compiled_bytes_per_device"]
    lanes = engine["lane_buckets"][-1]
    _experts_kernels_and_a_chunks_attend(compiled.as_text(), cfg, b, tc, cap)
    memory = compiled.memory_analysis()
    per_token = 2 * cfg.num_layers * sum(h * d for h, d in cfg.cache_arrays)
    assert per_token == 13056
    weights = memory.argument_size_in_bytes - per_token * b * cap
    assert 8.7e9 < weights < 8.8e9
    assert memory.temp_size_in_bytes < 0.6e9
    assert memory.argument_size_in_bytes == stated[form]["argument"]
    # the file's, which state the all-rows form (0.2 GB of a chunk's temporaries
    # lay in the logits' buffer there)
    assert _holds_no_more_than_stated(memory, stated[form])
    # beside the pool and the caches of the call in flight
    pool = per_token * engine["num_blocks"] * engine["block_size"]
    assert _device_bytes(compiled) + pool + per_token * lanes * cap < HBM_BYTES


def test_keye_vl2_prefill_makes_logits_for_the_row_it_reads_and_decode_is_as_it_was(
        shaped, built_for_tpu):
    """The engine's ``(1, 512, 32768)`` prefill program makes the head's product for
    the one row ``last`` names (PR 57): no float32 value of ``[lanes, tokens,
    vocabulary]`` anywhere in it, and what it hands back is smaller than the
    all-rows form's (the file's) by the 311 MB of those logits at least; the head's
    kernel goes into the ``cond`` as the parameter it is, uncopied. The ``(4, 1,
    32768)`` decode program, which has no ``cond`` at its head, holds no more
    temporaries than the file states."""
    cfg, config = _keye_stage()
    stated = config["compiled_bytes_per_device"]
    prefill, b, tc, _ = _keye_extend_at_its_largest(shaped, built_for_tpu, "prefill")
    text = prefill.as_text()
    assert f"f32[{b},{tc},{cfg.vocab_size}]" not in text
    assert f"f32[{b},{cfg.vocab_size}]" in text
    all_rows_logits = 4 * b * tc * cfg.vocab_size
    assert all_rows_logits == 311_164_928
    assert prefill.memory_analysis().output_size_in_bytes <= (
        stated["prefill"]["output"] - all_rows_logits)
    (head,) = [
        line for line in text.splitlines()
        if " conditional(" in line and "extend.logits/cond" in line]
    kernel = f"bf16[{cfg.embed_dim},{cfg.vocab_size}]"
    assert not [
        line for line in text.splitlines()
        if re.search(rf"= {re.escape(kernel)}\S* (copy|copy-start|transpose)\(", line)], head
    decode, *_ = _keye_extend_at_its_largest(shaped, built_for_tpu, "decode")
    assert "extend.logits/cond" not in decode.as_text()
    assert decode.memory_analysis().temp_size_in_bytes <= stated["decode"]["temp"]


def test_three_arena_paging_programs_compile_without_a_whole_arena_temporary(shaped):
    """The pool of the Keye-VL-2.0 configuration (512 blocks of 256 tokens, six
    layers, K and V of 4 x 128 and an indexer key of 1 x 64 a token: 1.71 GB in
    three arenas of two shapes) and the programs around ``extend``: as for two
    arenas, no program holds a temporary the size of a K or V arena (0.8 GB),
    and the page-back and the clone alias all three. The indexer's arena (0.1
    GB; rows of 64, half the chip's lanes, which the runtime lays out with a
    block's tokens along the lanes) is moved and written as it lies: no program
    holds a copy of it (until PR 55 the gather and the page-back each re-laid
    all of it out twice, on every call)."""
    cfg, config = _keye_stage()
    engine = config["engine"]
    blocks, block, tokens = engine["num_blocks"], engine["block_size"], engine["prefill_chunk"]
    arenas = tuple(
        shaped((cfg.num_layers, blocks, block) + tuple(each), cfg.dtype)
        for each in cfg.cache_arrays)
    per_token = 2 * cfg.num_layers * sum(h * d for h, d in cfg.cache_arrays)
    arena_bytes = per_token * blocks * block
    assert arena_bytes == 13056 * 131072
    narrow = 2 * cfg.num_layers * blocks * block * cfg.index_dim      # the indexer's arena
    assert narrow * 8 < arena_bytes
    programs = llm._paging_programs()
    width = llm._operand_width(tokens, engine["cache_buckets"][-1] // block)
    lanes = engine["lane_buckets"][-1]
    for b, cap in ((1, engine["cache_buckets"][0]), (lanes, engine["cache_buckets"][-1])):
        memory = programs.gather.lower(
            f"gather_{b}x{cap}", arenas, shaped((b, width), jnp.int32), cap // block
        ).compile().memory_analysis()
        assert 0 <= memory.output_size_in_bytes - per_token * b * cap < 4096 * 3
        assert memory.temp_size_in_bytes < 2**20, (b, cap)
    for b, tc in ((lanes, 1), (1, tokens)):
        news = tuple(
            shaped((cfg.num_layers, b, tc) + tuple(each), cfg.dtype) for each in cfg.cache_arrays)
        memory = programs.page_back.lower(
            f"page_back_{b}x{tc}", arenas, news, shaped((b, width), jnp.int32),
            shaped((b, cfg.vocab_size), jnp.float32),
            (shaped((len(cfg.counters),), jnp.int32),), lanes,
        ).compile().memory_analysis()
        assert memory.alias_size_in_bytes == arena_bytes, (b, tc)
        assert memory.temp_size_in_bytes < 2**20, (b, tc)
    clone = programs.clone.lower(
        arenas, shaped((), jnp.int32), shaped((), jnp.int32)).compile().memory_analysis()
    assert clone.alias_size_in_bytes == arena_bytes and clone.temp_size_in_bytes < 2**20


def _kimi_share():
    """The served cut of Kimi-K2-Instruct (layer 0 and six expert layers, 12 of
    384 experts, an eighth of the vocabulary) and its engine sizes, from the
    configuration's file."""
    import json

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "kimi-k2-instruct-serve-ep32.json")) as f:
        config = json.load(f)
    return kimi_k2.KimiK2Config(
        vocab_size=config["vocab_size"], num_layers=config["num_hidden_layers"],
        num_experts=config["n_routed_experts"]), config


def _kimi_extend_at(shaped, cfg, engine, b, tc, cap):
    """The configuration's ``extend`` as a step calls it, compiled for ``b``
    lanes of ``tc`` tokens over a cache of ``cap``, under the engine's name for it: over
    padded caches, or for a decode call of a model that reads pages (Kimi's, not GLM-5's)
    over the pool's arena and the lanes' block table."""
    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype), jax.eval_shape(lambda: cfg.init_params(0)))
    caches, statics = _caches_as_the_engine_hands_them(cfg, engine, shaped, b, tc, cap)
    operands = shaped(
        (b, llm._operand_width(engine["prefill_token_buckets"][-1], cap // engine["block_size"])),
        jnp.int32)
    home = shaped((engine["lane_buckets"][-1] + len(cfg.counters),), jnp.int32)
    return llm._operand_extend(cfg.make_extend_fn(), len(caches)).lower(
        llm._extend_name(b, tc, cap), params, operands, home, *caches, **statics).compile()


def test_every_compiled_program_has_a_module_name_of_its_own(shaped, built_for_tpu):
    """What a profile of the chip shows on its ``XLA Modules`` line: two of
    Kimi's ``extend`` shapes and two of its pool's gathers are four modules of
    four names, each the one the engine records for the call (``llm.dispatch``'s
    ``program``; ``stats()["programs"]``), none with a dot (a reader of a trace
    takes a dotted component of an ``op_name`` for a scope)."""
    built_for_tpu(True)
    cfg, config = _kimi_share()
    engine = config["engine"]
    block, small = engine["block_size"], engine["cache_buckets"][0]
    arenas = (shaped((cfg.num_layers, engine["num_blocks"], block, 1, cfg.row_dim), cfg.dtype),)
    width = llm._operand_width(engine["prefill_chunk"], engine["cache_buckets"][-1] // block)
    texts = [
        _kimi_extend_at(shaped, cfg, engine, b, tc, small).as_text()
        for b, tc in ((2, 1), (1, engine["prefill_token_buckets"][0]))
    ] + [
        llm._paging_programs().gather.lower(
            f"gather_{b}x{small}", arenas, shaped((b, width), jnp.int32), small // block
        ).compile().as_text()
        for b in (1, 2)
    ]
    names = [re.match(r"HloModule (jit_\w+),", text)[1] for text in texts]
    assert names == [
        f"jit_extend_decode_2x1x{small}",
        f"jit_extend_prefill_1x{engine['prefill_token_buckets'][0]}x{small}",
        f"jit_gather_1x{small}", f"jit_gather_2x{small}"]
    assert len(set(names)) == 4
    # every instruction's op_name starts with its program's name: no component of it a scope
    for name, text in zip(names, texts):
        inner = name[len("jit_"):]
        assert f'op_name="jit({inner})/' in text and "." not in inner


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_kimi_k2_share_extend_compiles_at_its_largest_shapes(shaped, form, built_for_tpu):
    """One chip's share of Kimi K2 at its published widths (9.70 GB of weights)
    over the largest cache bucket: a decode call of four lanes is handed the pool's
    arena (1.47 GB, not donated) and its block table and attends in the absorbed form
    in ``paged_attention`` over the lanes' pages where the pool keeps them (layer 0's
    call site and the scan's; a page fetched once, the value its rows' first 512
    features), so it holds no float32 score over the bucket, nothing of a padded
    cache's size (1.17 GB: gathered, then copied a layer's slab at a time to write
    the call's row; compile, PR 66) and under 8 MB of temporaries where it had 237; a
    prefill chunk attends in the expanded form in ``latent_attention`` (each
    tile of 640-wide rows through ``W_kvb`` in VMEM, eight heads at a time), and
    neither a float32 score of a chunk over the cache (4.3 GB a lane if it were)
    nor a head's keys or values of the cache's slots (0.54 GB a layer each) is
    left in the program; it fits beside the pool and a second call's caches,
    copies no layer's experts (1.1 GB) and holds the memory the configuration's
    file states."""
    built_for_tpu(True)     # the chip's grouped matmul and attention kernel
    cfg, config = _kimi_share()
    engine, stated = config["engine"], config["compiled_bytes_per_device"]
    cap, lanes = engine["cache_buckets"][-1], engine["lane_buckets"][-1]
    b, tc = (lanes, 1) if form == "decode" else (1, engine["prefill_token_buckets"][-1])
    assert stated[form]["shape"] == [b, tc, cap]
    compiled = _kimi_extend_at(shaped, cfg, engine, b, tc, cap)
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_extend_{form}_{b}x{tc}x{cap},")
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    experts = [line for line in kernels if "extend.moe.experts" in line]
    assert len(experts) >= 2                                     # the kernel is there
    if form == "prefill":
        # layer 0's attend and the scanned layers': straight under the scope the readers count
        attends = [line for line in kernels if line not in experts]
        assert len(attends) == 2 and all(
            "/extend.attention/latent_attention/" in line for line in attends)
        # ... and no float32 array over the cache as large as 32 queries' scores
        import math

        def over_cache(types):
            return max((
                math.prod(map(int, dims.split(",")))
                for dims in re.findall(rf"(?:{types})\[([0-9,]+)\]", text)
                if str(cap) in dims.split(",")), default=0)

        assert over_cache("f32") < cfg.num_heads * layers.QUERY_BLOCK * cap
        # ... nor the heads' keys or values of the cache's slots, in any type
        # (the expansion never leaves VMEM)
        assert over_cache("f32|bf16") < cap * cfg.num_heads * cfg.v_dim
    else:
        assert _attends_through_the_table(text, cfg, engine, sites=2) == experts
        # no lane's scores over the bucket, and no array a padded cache long
        assert f"f32[{lanes},{cfg.num_heads},1,{cap}]" not in text
        assert not re.search(rf"bf16\[(\d+,)*{cap},(\d+,)*{cfg.row_dim}\]", text)
    memory = compiled.memory_analysis()
    per_token = 2 * cfg.num_layers * sum(h * d for h, d in cfg.cache_arrays)
    assert per_token == 7 * 1280
    pool = per_token * engine["num_blocks"] * engine["block_size"]
    # the caches a call is handed: a chunk's gathered rows, a decode call's the pool
    # itself (the file's figure is the gathered form's: the benchmark's to bring up to date)
    handed = pool if form == "decode" else per_token * b * cap
    weights = memory.argument_size_in_bytes - handed
    assert 9.69e9 < weights < 9.71e9
    assert memory.argument_size_in_bytes == stated[form]["argument"] + handed - per_token * b * cap
    assert memory.temp_size_in_bytes <= stated[form]["temp"] * 1.05 < 0.4e9
    if form == "decode":
        assert memory.temp_size_in_bytes < 2**23 and stated[form]["temp"] - 2**23 > 0.2e9
    # beside the pool (a decode call's arguments hold it) and the caches of a chunk in flight
    assert _device_bytes(compiled) + (0 if form == "decode" else pool) + (
        per_token * engine["prefill_lanes"] * cap) < HBM_BYTES


def test_an_arena_of_latent_rows_pages_without_a_whole_arena_temporary(shaped):
    """The pool of the Kimi K2 configuration (640 blocks of 256 tokens, seven
    layers, one row of 1 x 640 a token: 1.47 GB in one arena) and the programs
    around ``extend``. The rows are moved without their heads axis and as they
    lie: no program holds a temporary the size of the arena or of a call's
    caches (1.17 GB), and the page-back and the clone alias the arena. What the
    64 spare features of a row bought until PR 55: a row of 576, one arena or
    two (512 and 64), was re-laid out by every gather; since then a row that is
    no whole number of 128 lanes is moved as the runtime lays it out (a block's
    tokens along the lanes), and neither form holds a copy (ROADMAP R3 (c))."""
    cfg, config = _kimi_share()
    engine = config["engine"]
    blocks, block, tokens = engine["num_blocks"], engine["block_size"], engine["prefill_chunk"]
    per_token = 2 * cfg.num_layers * cfg.row_dim
    arena_bytes = per_token * blocks * block
    assert (cfg.row_dim, per_token, blocks * block) == (640, 8960, 163840)
    programs = llm._paging_programs()
    width = llm._operand_width(tokens, engine["cache_buckets"][-1] // block)
    lanes = engine["lane_buckets"][-1]

    def arenas_of(*rows):
        return tuple(shaped((cfg.num_layers, blocks, block, 1, dim), cfg.dtype) for dim in rows)

    arenas = arenas_of(cfg.row_dim)
    for b, cap in ((1, engine["cache_buckets"][0]), (lanes, engine["cache_buckets"][-1])):
        compiled = programs.gather.lower(
            f"gather_{b}x{cap}", arenas, shaped((b, width), jnp.int32), cap // block).compile()
        assert compiled.as_text().startswith(f"HloModule jit_gather_{b}x{cap},")
        memory = compiled.memory_analysis()
        assert 0 <= memory.output_size_in_bytes - per_token * b * cap < 4096
        assert memory.temp_size_in_bytes < 2**20, (b, cap)
    for b, tc in ((lanes, 1), (1, tokens)):
        news = (shaped((cfg.num_layers, b, tc, 1, cfg.row_dim), cfg.dtype),)
        memory = programs.page_back.lower(
            f"page_back_{b}x{tc}", arenas, news, shaped((b, width), jnp.int32),
            shaped((b, cfg.vocab_size), jnp.float32),
            (shaped((len(cfg.counters),), jnp.int32),), lanes,
        ).compile().memory_analysis()
        assert memory.alias_size_in_bytes == arena_bytes, (b, tc)
        assert memory.temp_size_in_bytes < 2**20, (b, tc)
    clone = programs.clone.lower(
        arenas, shaped((), jnp.int32), shaped((), jnp.int32)).compile().memory_analysis()
    assert clone.alias_size_in_bytes == arena_bytes and clone.temp_size_in_bytes < 2**20
    # a row of 576 in one arena: the runtime lays it out with the block's tokens
    # innermost, and the gather moves it so (flat, it re-laid all of it out, twice
    # over: 2.6 GB of temporaries; in two arenas the 64-wide one, 0.15 GB)
    small = (shaped((1, width), jnp.int32), engine["cache_buckets"][0] // block)
    one = programs.gather.lower(
        "gather_rows_of_576", arenas_of(576), *small).compile().memory_analysis()
    assert one.temp_size_in_bytes < 2**20
    two = programs.gather.lower(
        "gather_rows_of_512_and_64", arenas_of(512, 64), *small).compile().memory_analysis()
    assert two.temp_size_in_bytes < 2**20


def _granite_whole():
    """granite-4.0-h-micro as served, whole, and its engine sizes, from the
    configuration's file."""
    import json

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "granite-4.0-h-micro-serve.json")) as f:
        config = json.load(f)
    return granitemoehybrid.GraniteMoeHybridConfig(), config


#: one sequence's state, all 36 Mamba layers: 64 x 64 x 128 float32 and 3 x 4352 bfloat16
GRANITE_STATE_BYTES = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)


def _granite_arenas(shaped, cfg, slots):
    return tuple(
        shaped((layers, slots) + tuple(shape), dtype) for layers, shape, dtype in cfg.state_arrays)


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_granite_hybrid_extend_compiles_at_its_largest_shapes(shaped, form, built_for_tpu):
    """The whole published model (6.38 GB of weights) over the largest cache
    bucket, on the pool's state arenas themselves (49 slots of 76.4 MB: 3.75
    GB, donated): a decode call of eight lanes, whose recurrence is the kernel
    ``ssm_step`` straight under ``extend.ssm.scan`` (a lane's state fetched from
    its slot and written back there) and whose four attention layers read the
    lanes' pages in the pool's arenas through the block table (``paged_attention``,
    heads of 64: no padded cache, no copy of an arena or of a layer's slab of
    one), a prefill chunk through the chunked
    recurrence and, in the four attention layers, the attention kernel. The
    arenas are aliased, all of them, and the program holds no temporary the size
    of one lane's state a lane, let alone an arena's; no layer's weights are
    copied for the scan (1.5 GB of temporaries when they were). It fits beside
    the pool's blocks and a second call's caches. (The byte counts are this
    test's own: the configuration file's ``compiled_bytes_per_device`` dates
    from the store that copied, and is the benchmark's to bring up to date.)"""
    built_for_tpu(True)
    cfg, config = _granite_whole()
    engine = config["engine"]
    cap, lanes, slots = engine["cache_buckets"][-1], engine["lane_buckets"][-1], engine["state_slots"]
    b, tc = (lanes, 1) if form == "decode" else (1, engine["prefill_token_buckets"][-1])
    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype), jax.eval_shape(lambda: cfg.init_params(0)))
    caches, statics = _caches_as_the_engine_hands_them(cfg, engine, shaped, b, tc, cap)
    arenas = _granite_arenas(shaped, cfg, slots)
    operands = shaped(
        (b, llm._operand_width(
            engine["prefill_token_buckets"][-1], cap // engine["block_size"], True)), jnp.int32)
    compiled = llm._operand_extend(cfg.make_extend_fn(), len(caches), len(arenas)).lower(
        llm._extend_name(b, tc, cap), params, operands,
        shaped((lanes + len(cfg.counters),), jnp.int32), *caches, *arenas, **statics
    ).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    if form == "prefill":
        assert kernels and all("/extend.attention/masked_attention/" in k for k in kernels)
    else:
        # nine Mamba layers a period and its attention layer, in the one scan body
        others = _attends_through_the_table(text, cfg, engine, sites=1)
        assert len(others) == 9 and all(
            "/extend.ssm.scan/jit(ssm_step_slots)/ssm_step/" in k for k in others)
    memory = compiled.memory_analysis()
    assert GRANITE_STATE_BYTES == 76_437_504
    # (the compiler pads the convolution's three rows: 0.14 % more than the values)
    arena_bytes = slots * GRANITE_STATE_BYTES
    assert 0 <= memory.alias_size_in_bytes - arena_bytes < slots * 2**17
    resident = engine["num_blocks"] * engine["block_size"] * 8192
    # the caches a call is handed: a chunk's gathered rows, a decode call's the pool itself
    handed = resident if form == "decode" else b * cap * 8192
    weights = memory.argument_size_in_bytes - memory.alias_size_in_bytes - handed - b * 2**17
    assert 6.38e9 < weights < 6.39e9
    # 4 MB (82 MB with eight lanes' padded caches re-laid out, before PR 61) and 9 MB;
    # the lanes' convolution inputs, 0.9 MB a lane, are the states' only part in them
    assert memory.temp_size_in_bytes < {"decode": 0.008e9, "prefill": 0.02e9}[form]
    assert memory.temp_size_in_bytes < b * GRANITE_STATE_BYTES
    # beside the pool's blocks (a decode call's argument) and a chunk's caches in flight
    assert _device_bytes(compiled) + (0 if form == "decode" else resident) + cap * 8192 < HBM_BYTES


def _granite_small_share():
    """The served cut of granite-4.0-h-small (one period, 36 of 72 experts, half
    the vocabulary) and its engine sizes, from the configuration's file."""
    import json

    from benchmark.manifest import published_keys
    from benchmark.models import granitemoehybrid_moe

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "granite-4.0-h-small-serve-ep2.json")) as f:
        config = json.load(f)
    return granitemoehybrid_moe.program_config(published_keys(config)), config


#: one sequence's state, the period's 9 Mamba layers: 128 x 64 x 128 float32 and 3 x 8448 bfloat16
GRANITE_SMALL_STATE_BYTES = 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
#: one layer's held experts: 36 of 3 x 4096 x 768 in bfloat16
GRANITE_SMALL_LAYER_EXPERTS_BYTES = 36 * 3 * 4096 * 768 * 2


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_granite_small_share_extend_compiles_and_copies_no_layers_experts(
        shaped, form, built_for_tpu):
    """One chip's share of granite-4.0-h-small at its published widths (9.51 GB
    of weights) over the largest cache bucket, on the pool's state arenas
    themselves (57 slots of 38.2 MB: 2.18 GB, donated and aliased): both
    families of mechanism in one program. A decode call of eight lanes runs the
    kernel ``ssm_step`` in each of the nine Mamba layers, the grouped matmul
    twice in each of the ten expert layers and, in the attention layer,
    ``paged_attention`` over the lanes' pages where the pool keeps them (no padded
    cache: its argument is the pool, its temporaries 21 MB where the re-laid-out
    caches were 289); a prefill chunk the chunked
    recurrence, the attention kernel and the same grouped matmuls. The held
    experts are read in place in their stacks: the temporaries stay far under
    one layer's 680 MB of them, which a scan that sliced them out copied on
    every call. It holds the memory the configuration's file states and fits
    beside the pool's blocks and a second call's caches."""
    built_for_tpu(True)
    cfg, config = _granite_small_share()
    engine, stated = config["engine"], config["compiled_bytes_per_device"]
    cap, lanes, slots = engine["cache_buckets"][-1], engine["lane_buckets"][-1], engine["state_slots"]
    b, tc = (lanes, 1) if form == "decode" else (1, engine["prefill_token_buckets"][-1])
    assert stated[form]["shape"] == [b, tc, cap]
    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype), jax.eval_shape(lambda: cfg.init_params(0)))
    caches, statics = _caches_as_the_engine_hands_them(cfg, engine, shaped, b, tc, cap)
    arenas = _granite_arenas(shaped, cfg, slots)
    operands = shaped(
        (b, llm._operand_width(
            engine["prefill_token_buckets"][-1], cap // engine["block_size"], True)), jnp.int32)
    compiled = llm._operand_extend(cfg.make_extend_fn(), len(caches), len(arenas)).lower(
        llm._extend_name(b, tc, cap), params, operands,
        shaped((lanes + len(cfg.counters),), jnp.int32), *caches, *arenas, **statics
    ).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    experts = [line for line in kernels if "/extend.moe.experts/" in line]
    assert len(experts) == 2 * cfg.period                        # two grouped matmuls a layer
    if form == "prefill":
        others = [line for line in kernels if line not in experts]
        assert others and all("/extend.attention/masked_attention/" in k for k in others)
    else:
        others = [
            k for k in _attends_through_the_table(text, cfg, engine, sites=1) if k not in experts]
        assert len(others) == 9 and all(
            "/extend.ssm.scan/jit(ssm_step_slots)/ssm_step/" in k for k in others)
    memory = compiled.memory_analysis()
    assert cfg.num_params() == 4_757_211_776 and GRANITE_SMALL_STATE_BYTES == 38_204_928
    arena_bytes = slots * GRANITE_SMALL_STATE_BYTES
    assert 0 <= memory.alias_size_in_bytes - arena_bytes < slots * 2**17
    per_token = 2 * cfg.cache_layers * 8 * 128 * 2
    assert per_token == 4096
    resident = engine["num_blocks"] * engine["block_size"] * per_token
    # the caches a call is handed: a chunk's gathered rows, a decode call's the pool
    # itself (the file's figure is the gathered form's: the benchmark's to bring up to date)
    handed = resident if form == "decode" else b * cap * per_token
    weights = memory.argument_size_in_bytes - memory.alias_size_in_bytes - handed - b * 2**17
    assert 9.51e9 < weights < 9.52e9
    assert memory.argument_size_in_bytes == stated[form]["argument"] + handed - b * cap * per_token
    assert _holds_no_more_than_stated(memory, stated[form])
    # no copy of a layer's experts (a chunk's are its pairs' rows and what lay in the
    # all-rows logits' buffer), and a decode call holds no padded cache: 21 MB
    assert memory.temp_size_in_bytes < (
        2**25 if form == "decode"
        else GRANITE_SMALL_LAYER_EXPERTS_BYTES / 2 + _all_rows_bytes(cfg, b, tc))
    # beside the pool's blocks (a decode call's argument) and a chunk's caches in flight
    assert _device_bytes(compiled) + (0 if form == "decode" else resident) + (
        cap * per_token) < HBM_BYTES


def test_state_slots_are_read_and_written_without_a_whole_arena_temporary(shaped, built_for_tpu):
    """The state arenas of the granite configuration (49 slots of 76.4 MB:
    3.75 GB) and the two programs that touch them: the copy (a prefix hit's
    restore) aliases the arenas and holds no temporary of any size; a decode
    ``extend`` of one lane aliases them and holds less than a lane's state."""
    built_for_tpu(True)
    cfg, config = _granite_whole()
    engine = config["engine"]
    slots = engine["state_slots"]
    arenas = _granite_arenas(shaped, cfg, slots)
    arena_bytes = slots * GRANITE_STATE_BYTES
    copy = llm._state_programs().copy.lower(
        arenas, shaped((), jnp.int32), shaped((), jnp.int32)).compile().memory_analysis()
    assert 0 <= copy.alias_size_in_bytes - arena_bytes < slots * 2**17
    assert copy.temp_size_in_bytes < 2**20
    cap = engine["cache_buckets"][0]
    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype), jax.eval_shape(lambda: cfg.init_params(0)))
    caches = [
        shaped((cfg.cache_layers, 1, cap) + tuple(each), cfg.dtype) for each in cfg.cache_arrays]
    operands = shaped(
        (1, llm._operand_width(
            engine["prefill_chunk"], engine["cache_buckets"][-1] // engine["block_size"], True)),
        jnp.int32)
    lanes = engine["lane_buckets"][-1]
    memory = llm._operand_extend(cfg.make_extend_fn(), len(caches), len(arenas)).lower(
        llm._extend_name(1, 1, cap), params, operands,
        shaped((lanes + len(cfg.counters),), jnp.int32), *caches, *arenas, tc=1
    ).compile().memory_analysis()
    assert 0 <= memory.alias_size_in_bytes - arena_bytes < slots * 2**17
    # 64 MB at one lane and 72 at eight: none of it a lane's state
    assert memory.temp_size_in_bytes < GRANITE_STATE_BYTES


def _minicpm_sala_stage():
    """The served cut of MiniCPM-SALA (one pipeline stage of two: four periods of a
    block-sparse layer and three lightning layers) and its engine sizes, from the
    configuration's file."""
    import json

    from benchmark.manifest import published_keys
    from benchmark.models import minicpm_sala as arch

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "minicpm-sala-serve-pp2.json")) as f:
        config = json.load(f)
    return arch.program_config(published_keys(config)), config


#: one sequence's state, the stage's 12 lightning layers: 32 x 128 x 128 float32
MINICPM_SALA_STATE_BYTES = 12 * 32 * 128 * 128 * 4
#: the smallest layer's weights: a sparse layer's 253,763,840 parameters in bfloat16
MINICPM_SALA_LAYER_BYTES = 253_763_840 * 2


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_minicpm_sala_stage_extend_compiles_and_copies_no_arena_and_no_layer(
        shaped, form, built_for_tpu):
    """One pipeline stage of MiniCPM-SALA at its published widths (10.08 GB of weights)
    over the largest cache bucket, with the compressed keys' cache at its own grain (a
    row for every 16 tokens) and on the pool's state arena itself (64 slots of 25.2 MB:
    1.61 GB, donated and aliased). A decode call of four lanes scores the compressed
    keys, gathers its blocks' rows and steps the recurrence where the slots lie, in
    XLA alone; a prefill chunk attends under a mask a K/V head in the kernel the other
    architectures call (once a period: one sparse layer). Neither holds a copy of the
    state arena or of a layer's weights, and both fit beside the pool's blocks and a
    second call's caches."""
    built_for_tpu(True)
    cfg, config = _minicpm_sala_stage()
    engine, stated = config["engine"], config["compiled_bytes_per_device"]
    cap, lanes, slots = engine["cache_buckets"][-1], engine["lane_buckets"][-1], engine["state_slots"]
    b, tc = (lanes, 1) if form == "decode" else (1, engine["prefill_token_buckets"][-1])
    assert stated[form]["shape"] == [b, tc, cap]
    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype), jax.eval_shape(lambda: cfg.init_params(0)))
    assert cfg.cache_arrays == ((1, 256), (1, 256), (1, 256, 16))
    caches = [
        shaped((cfg.cache_layers, b, cap // llm.cache_grain(each)) + each[:2], cfg.dtype)
        for each in cfg.cache_arrays]
    arenas = tuple(
        shaped((layers, slots) + shape, dtype) for layers, shape, dtype in cfg.state_arrays)
    operands = shaped(
        (b, llm._operand_width(
            engine["prefill_token_buckets"][-1], cap // engine["block_size"], True)), jnp.int32)
    compiled = llm._operand_extend(cfg.make_extend_fn(), len(caches), len(arenas)).lower(
        llm._extend_name(b, tc, cap), params, operands,
        shaped((lanes + len(cfg.counters),), jnp.int32), *caches, *arenas, tc=tc
    ).compile()
    kernels = [
        line for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line]
    if form == "prefill":
        assert len(kernels) == 1 and "/extend.attention/masked_attention/" in kernels[0]
    else:
        assert not kernels
    memory = compiled.memory_analysis()
    assert cfg.num_params() == 5_039_400_832 and MINICPM_SALA_STATE_BYTES == 25_165_824
    arena_bytes = slots * MINICPM_SALA_STATE_BYTES
    assert 0 <= memory.alias_size_in_bytes - arena_bytes < slots * 2**17
    per_token = cfg.cache_layers * (2 * 256 * 2 + 256 * 2 // 16)
    assert per_token == 4224
    weights = memory.argument_size_in_bytes - memory.alias_size_in_bytes - b * (
        cap * per_token + 2**17)
    assert 10.07e9 < weights < 10.09e9
    assert memory.argument_size_in_bytes == stated[form]["argument"]
    assert _holds_no_more_than_stated(memory, stated[form])
    # no copy of the state arena, of a lane's share of it times the lanes, or of a layer
    assert memory.temp_size_in_bytes < MINICPM_SALA_LAYER_BYTES / 2
    assert memory.temp_size_in_bytes < arena_bytes / 8
    resident = engine["num_blocks"] * engine["block_size"] * per_token
    assert _device_bytes(compiled) + resident + lanes * cap * per_token < HBM_BYTES


def test_a_coarse_arena_pages_without_a_whole_arena_temporary(shaped):
    """The three arenas of the MiniCPM-SALA stage's pool (K and V a token, compressed
    keys a row for every 16: 0.69 GB) and the three programs that touch them, compiled
    beside nothing: the page-back aliases the arenas and its temporaries are far
    smaller than the smallest arena, the coarse one's conditional write included."""
    cfg, config = _minicpm_sala_stage()
    engine = config["engine"]
    blocks, block = engine["num_blocks"], engine["block_size"]
    arenas = tuple(
        shaped((cfg.cache_layers, blocks, block // llm.cache_grain(each)) + each[:2], cfg.dtype)
        for each in cfg.cache_arrays)
    arena_bytes = [math.prod(a.shape) * 2 for a in arenas]
    assert arena_bytes == [335_544_320, 335_544_320, 20_971_520]
    programs = llm._paging_programs()
    b, tc, cap = 1, engine["prefill_token_buckets"][-1], engine["cache_buckets"][-1]
    width = llm._operand_width(tc, cap // block, True)
    operands = shaped((b, width), jnp.int32)
    news = tuple(
        shaped((cfg.cache_layers, b, -(-tc // llm.cache_grain(each))) + each[:2], cfg.dtype)
        for each in cfg.cache_arrays)
    logits = shaped((b, cfg.vocab_size), jnp.float32)
    counted = (shaped((len(cfg.counters),), jnp.int32),)
    back = programs.page_back.lower(
        f"page_back_{b}x{tc}", arenas, news, operands, logits, counted, 4
    ).compile().memory_analysis()
    assert back.alias_size_in_bytes >= sum(arena_bytes)
    assert back.temp_size_in_bytes < min(arena_bytes) / 2
    gather = programs.gather.lower(
        f"gather_{b}x{cap}", arenas, operands, cap // block).compile().memory_analysis()
    assert gather.temp_size_in_bytes < min(arena_bytes)
    assert 0 <= gather.output_size_in_bytes - cap * 4224 < 2**12
    clone = programs.clone.lower(
        arenas, shaped((), jnp.int32), shaped((), jnp.int32)).compile().memory_analysis()
    assert clone.temp_size_in_bytes < 2**21


def _mimo_v2_flash_share():
    """The served cut of MiMo-V2-Flash (one chip of ep16 x pp8: layer 0 and one period
    of five sliding layers and a full one, 16 of 256 experts) and its engine sizes,
    from the configuration's file."""
    import json

    from benchmark.manifest import published_keys
    from benchmark.models import mimo_v2_flash as arch

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "mimo-v2-flash-serve-ep16.json")) as f:
        config = json.load(f)
    return arch.program_config(published_keys(config)), config


#: one sequence's windows, the cut's 5 sliding layers: 128 rows of 8 x (192 + 128) bfloat16
MIMO_WINDOW_BYTES = 5 * 128 * 8 * (192 + 128) * 2
#: one expert layer's 16 held experts in bfloat16
MIMO_EXPERTS_BYTES = 402_653_184 * 2


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_mimo_v2_flash_share_extend_compiles_and_copies_no_arena_and_no_expert(
        shaped, form, built_for_tpu):
    """One chip's share of MiMo-V2-Flash at its published widths (6.86 GB of weights)
    over the largest cache bucket, the two full layers' rows (5,120 B a token) gathered
    for a chunk and read where the pool keeps them by a decode call, and the five
    sliding layers' windows read and written where the pool's state
    arenas lie (512 slots of 3.28 MB: 1.68 GB, donated and aliased). A decode call of
    eight lanes is handed the pool's arenas (2.10 GB, not donated) and its block table:
    its two full layers attend in ``paged_attention`` (layer 0's call site and the
    scanned period's), keys of 192 beside values of 128, and its temporaries are 5.6 MB
    where the padded caches, written into and re-laid out by K/V head, were 1.41 GB
    beside 1.34 GB of gathered rows (compile, PR 61); its other kernels are the grouped
    matmuls of the six expert layers (in the period's scan and beside it); the sliding
    layers' decode attend stays XLA's; a prefill chunk attends in the
    kernel the other architectures call, three call sites (layer 0, the period's full
    layer, the scanned sliding layers' with ``sinks``), K wider than V. Neither holds
    a copy of a window arena (left free, the compiler carries all of it through the
    scan in another layout: 1.68 GB in and out a call) or of a layer's experts, and both
    fit beside the pool's blocks and a second call's caches."""
    built_for_tpu(True)
    cfg, config = _mimo_v2_flash_share()
    engine, stated = config["engine"], config["compiled_bytes_per_device"]
    cap, lanes, slots = engine["cache_buckets"][-1], engine["lane_buckets"][-1], engine["state_slots"]
    b, tc = (lanes, 1) if form == "decode" else (1, engine["prefill_token_buckets"][-1])
    assert stated[form]["shape"] == [b, tc, cap]
    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype), jax.eval_shape(lambda: cfg.init_params(0)))
    assert cfg.cache_arrays == ((1, 768), (1, 512)) and cfg.cache_layers == 2
    caches, statics = _caches_as_the_engine_hands_them(cfg, engine, shaped, b, tc, cap)
    assert (form == "decode") == ("pages" in statics)
    arenas = tuple(
        shaped((layers, slots) + shape, dtype) for layers, shape, dtype in cfg.state_arrays)
    assert [a.shape for a in arenas] == [(5, slots, 128, 1536), (5, slots, 128, 1024)]
    operands = shaped(
        (b, llm._operand_width(
            engine["prefill_token_buckets"][-1], cap // engine["block_size"], True)), jnp.int32)
    compiled = llm._operand_extend(cfg.make_extend_fn(), len(caches), len(arenas)).lower(
        llm._extend_name(b, tc, cap), params, operands,
        shaped((lanes + len(cfg.counters),), jnp.int32), *caches, *arenas, **statics
    ).compile()
    text = compiled.as_text()
    kernels = [
        line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    if form == "prefill":
        attends = [k for k in kernels if "/masked_attention/" in k]
        assert len(attends) == 3
        assert sum("/extend.attention.window/masked_attention/" in k for k in attends) == 1
        assert sum("/extend.attention/masked_attention/" in k for k in attends) == 2
        others = [k for k in kernels if k not in attends]
    else:
        others = _attends_through_the_table(text, cfg, engine, sites=2)
    assert len(others) == 4 and all("/extend.moe.experts/" in k for k in others)
    # the arenas keep their layout through both scans: a row's features innermost
    assert set(re.findall(r"bf16\[5,%d,128,\d+\]\{([\d,]+)" % slots, text)) == {"3,2,1,0"}
    memory = compiled.memory_analysis()
    assert cfg.num_params() == 3_429_955_392 and MIMO_WINDOW_BYTES == 3_276_800
    arena_bytes = slots * MIMO_WINDOW_BYTES
    assert 0 <= memory.alias_size_in_bytes - arena_bytes < 2**20
    per_token = cfg.cache_layers * (768 + 512) * 2
    assert per_token == 5120
    resident = engine["num_blocks"] * engine["block_size"] * per_token
    # the caches a call is handed: a chunk's gathered rows, a decode call's the pool
    # itself (the file's figure is the gathered form's: the benchmark's to bring up to date)
    handed = resident if form == "decode" else b * cap * per_token
    weights = memory.argument_size_in_bytes - memory.alias_size_in_bytes - handed - b * 2**17
    assert 6.85e9 < weights < 6.87e9
    assert memory.argument_size_in_bytes == stated[form]["argument"] + handed - b * cap * per_token
    assert _holds_no_more_than_stated(memory, stated[form])
    # no copy of a window arena or of a layer's experts, and a decode call holds nothing
    # of a padded cache's size: 5.6 MB, where 1.41 GB were its lanes' K and V written
    # into and re-laid out by K/V head
    assert memory.temp_size_in_bytes < (
        2**23 if form == "decode" else min(MIMO_EXPERTS_BYTES, arena_bytes * 0.6))
    assert stated["decode"]["temp"] - 2**23 > 1e9
    # beside the pool's blocks, which a decode call's arguments hold
    assert _device_bytes(compiled) + (0 if form == "decode" else resident) < HBM_BYTES
    assert 2 * cfg.num_params() + resident + arena_bytes >= 0.60 * HBM_BYTES


def _qwen3_next_share():
    """The served cut of Qwen3-Next (one chip of ep4 x pp6: two periods of three delta
    layers and a full one, 128 of 512 experts) and its engine sizes, from the
    configuration's file."""
    import json

    from benchmark.manifest import published_keys
    from benchmark.models import qwen3_next as arch

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "qwen3-next-80b-a3b-serve-ep4.json")) as f:
        config = json.load(f)
    return arch.program_config(published_keys(config)), config


#: one sequence's state, the cut's 6 delta layers: 32 heads of 128 x 128 float32 and the
#: convolution's last 3 inputs of 8,192 channels in bfloat16
QWEN3_NEXT_STATE_BYTES = 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
#: one layer's 128 held experts in bfloat16
QWEN3_NEXT_EXPERTS_BYTES = 402_653_184 * 2


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_qwen3_next_share_extend_compiles_and_copies_no_arena_and_no_expert(
        shaped, form, built_for_tpu):
    """One chip's share of Qwen3-Next at its published widths (7.33 GB of weights) over
    the largest cache bucket, the two full layers' rows gathered (4,096 B a token) and
    the six delta layers' state and convolution tail read and written where the pool's
    state arenas lie (160 slots of 12.9 MB: 2.06 GB, donated and aliased). A decode
    call of sixteen lanes is handed the pool's arenas (1.34 GB) and its block table and
    attends at a head of 256 in ``paged_attention`` (one call site, the scanned period's
    full layer; 49 MB of temporaries where the re-laid-out padded caches were 841),
    beside the grouped matmuls of the
    eight expert layers (two a layer, a period's four layers in the scan's body); a
    prefill chunk of 1,024 attends at a head of 256 in the kernel the other architectures
    call, and runs the delta rule's sixteen sub-chunks as a loop of its own. Neither
    holds a copy of a state arena or of a layer's experts, no array of a delta layer
    grows with the context, and both fit beside the pool's blocks and a second call's
    caches."""
    built_for_tpu(True)
    cfg, config = _qwen3_next_share()
    engine, stated = config["engine"], config["compiled_bytes_per_device"]
    cap, lanes, slots = engine["cache_buckets"][-1], engine["lane_buckets"][-1], engine["state_slots"]
    b, tc = (lanes, 1) if form == "decode" else (1, engine["prefill_token_buckets"][-1])
    assert stated[form]["shape"] == [b, tc, cap] and (lanes, tc in (1, 1024)) == (16, True)
    params = jax.tree.map(
        lambda x: shaped(x.shape, x.dtype), jax.eval_shape(lambda: cfg.init_params(0)))
    assert cfg.cache_arrays == ((1, 512), (1, 512)) and cfg.cache_layers == 2
    caches, statics = _caches_as_the_engine_hands_them(cfg, engine, shaped, b, tc, cap)
    assert (form == "decode") == ("pages" in statics)
    arenas = tuple(
        shaped((layers, slots) + shape, dtype) for layers, shape, dtype in cfg.state_arrays)
    assert [a.shape for a in arenas] == [(6, slots, 32, 128, 128), (6, slots, 3, 8192)]
    operands = shaped(
        (b, llm._operand_width(
            engine["prefill_token_buckets"][-1], cap // engine["block_size"], True)), jnp.int32)
    compiled = llm._operand_extend(cfg.make_extend_fn(), len(caches), len(arenas)).lower(
        llm._extend_name(b, tc, cap), params, operands,
        shaped((lanes + len(cfg.counters),), jnp.int32), *caches, *arenas, **statics
    ).compile()
    text = compiled.as_text()
    kernels = [
        line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    if form == "prefill":
        attends = [k for k in kernels if "/masked_attention/" in k]
        assert len(attends) == 1 and "/extend.attention/masked_attention/" in attends[0]
        others = [k for k in kernels if k not in attends]
    else:
        others = _attends_through_the_table(text, cfg, engine, sites=1)
    assert len(others) == 8 and all("/extend.moe.experts/" in k for k in others)
    # the state arena keeps its layout through the scan: a head's 128 x 128 innermost
    assert set(re.findall(r"f32\[6,%d,32,128,128\]\{([\d,]+)" % slots, text)) == {"4,3,2,1,0"}
    memory = compiled.memory_analysis()
    assert cfg.num_params() == 3_667_251_328 and QWEN3_NEXT_STATE_BYTES == 12_877_824
    arena_bytes = slots * QWEN3_NEXT_STATE_BYTES
    assert 0 <= memory.alias_size_in_bytes - arena_bytes < 2**20
    per_token = cfg.cache_layers * (512 + 512) * 2
    assert per_token == 4096
    resident = engine["num_blocks"] * engine["block_size"] * per_token
    # the caches a call is handed: a chunk's gathered rows, a decode call's the pool
    # itself (the file's figure is the gathered form's: the benchmark's to bring up to date)
    handed = resident if form == "decode" else b * cap * per_token
    weights = memory.argument_size_in_bytes - memory.alias_size_in_bytes - handed - b * 2**17
    assert 7.32e9 < weights < 7.35e9
    assert memory.argument_size_in_bytes == stated[form]["argument"] + handed - b * cap * per_token
    assert _holds_no_more_than_stated(memory, stated[form])
    # no copy of a state arena or of a layer's experts; a decode call's temporaries are
    # its sixteen lanes' states (2 MB a lane and layer) in flight and nothing of a padded
    # cache's size: 49 MB
    assert memory.temp_size_in_bytes < (
        2**26 if form == "decode" else min(QWEN3_NEXT_EXPERTS_BYTES, arena_bytes / 4))
    # beside the pool's blocks (a decode call's argument) and a chunk's caches in flight
    assert _device_bytes(compiled) + (0 if form == "decode" else resident) + (
        cap * per_token) < HBM_BYTES
    assert 2 * cfg.num_params() + resident + arena_bytes >= 0.60 * HBM_BYTES


def _glm_share():
    """The served cut of GLM-5 (layer 0 and five expert layers, 16 of 256 experts, an
    eighth of the vocabulary) and its engine sizes, from the configuration's file."""
    import json

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs", "glm-5-serve-ep16.json")) as f:
        config = json.load(f)
    return glm_moe_dsa.GlmMoeDsaConfig(
        vocab_size=config["vocab_size"], num_layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        num_experts=config["n_routed_experts"]), config


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_glm_5_share_extend_compiles_at_its_largest_shapes(shaped, form, built_for_tpu):
    """One chip's share of GLM-5 at its published widths (9.45 GB of weights) over the
    largest cache bucket, both arenas: a decode call scores its lanes' indexer keys, takes
    2,048 rows a lane and attends those in the absorbed form, all in XLA; a prefill chunk
    attends in the expanded form in ``latent_attention`` (a head's ``W_kvb^K`` columns are
    192 wide and start inside a lane tile: the heads' slabs) under the selection mask, and
    neither a float32 score of all a chunk's queries and heads over the cache nor a head's
    keys or values of the cache's slots is left in the program; it fits beside the pool
    and a second call's caches, and holds the memory the configuration's file states."""
    built_for_tpu(True)     # the chip's grouped matmul and attention kernel
    cfg, config = _glm_share()
    engine, stated = config["engine"], config["compiled_bytes_per_device"]
    cap, lanes = engine["cache_buckets"][-1], engine["lane_buckets"][-1]
    b, tc = (lanes, 1) if form == "decode" else (1, engine["prefill_token_buckets"][-1])
    assert stated[form]["shape"] == [b, tc, cap]
    compiled = _kimi_extend_at(shaped, cfg, engine, b, tc, cap)
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_extend_{form}_{b}x{tc}x{cap},")
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    experts = [line for line in kernels if "extend.moe.experts" in line]
    assert len(experts) >= 2                                     # the kernel is there
    if form == "prefill":
        # layer 0's attend and the scanned layers': straight under the scope the readers count
        attends = [line for line in kernels if line not in experts]
        assert len(attends) == 2 and all(
            "/extend.attention/latent_attention/" in line for line in attends)

        def over_cache(types):
            return max((
                math.prod(map(int, dims.split(",")))
                for dims in re.findall(rf"(?:{types})\[([0-9,]+)\]", text)
                if str(cap) in dims.split(",")), default=0)

        # the largest float32 array over the cache: 32 queries' dots of the indexer's heads
        assert over_cache("f32") <= cfg.index_heads * layers.QUERY_BLOCK * cap
        # ... and no head's keys or values of the cache's slots, in any type
        assert over_cache("f32|bf16") < cap * cfg.num_heads * cfg.v_dim
    else:
        assert kernels == experts
        # a decode lane's index scores over its cache, and its attend over the rows it took
        assert f"f32[{lanes},{cfg.index_heads},{cap}]" in text
        assert f"f32[{lanes},{cfg.num_heads},1,{cfg.topk}]" in text
        assert f"f32[{lanes},{cfg.num_heads},1,{cap}]" not in text
    memory = compiled.memory_analysis()
    per_token = 2 * cfg.num_layers * sum(h * d for h, d in cfg.cache_arrays)
    assert per_token == 6 * (1280 + 256)
    weights = memory.argument_size_in_bytes - per_token * b * cap
    assert 9.45e9 < weights < 9.46e9
    assert memory.argument_size_in_bytes == stated[form]["argument"]
    assert memory.temp_size_in_bytes <= stated[form]["temp"] * 1.05 < 0.5e9
    # beside the pool and the caches of the call in flight
    pool = per_token * engine["num_blocks"] * engine["block_size"]
    assert _device_bytes(compiled) + pool + per_token * lanes * cap < HBM_BYTES


def _longcat_share():
    """The served cut of LongCat-Flash-Chat (four layers of two sub-blocks, 16 of 512
    routed experts beside the 256 zero-compute outputs, an eighth of the vocabulary) and
    its engine sizes, from the configuration's file."""
    import json

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "longcat-flash-chat-serve-ep32.json")) as f:
        config = json.load(f)
    return longcat_flash.LongcatFlashConfig(
        vocab_size=config["vocab_size"], num_layers=config["num_layers"],
        num_experts=config["n_routed_experts"]), config


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_longcat_flash_share_extend_compiles_at_its_largest_shapes(shaped, form, built_for_tpu):
    """One chip's share of LongCat-Flash-Chat at its published widths (10.35 GB of
    weights) over the largest cache bucket: a decode call of **sixteen** lanes is handed
    the pool's arena of eight slabs (two a layer, not donated) and its block table and
    attends in the absorbed form in ``paged_attention``, one call site a sub-block, so it
    holds no score over the bucket and nothing of a padded cache's size; a prefill chunk
    attends in the expanded form in ``latent_attention``, one call site a sub-block, over
    rows that hold the scaled latent (no kernel knows of ``mla_scale_kv_lora``); the held
    experts run in the grouped matmul's kernel and the zero-compute picks in none; neither
    copies a layer's experts (1.2 GB) or holds more than the configuration's file states."""
    built_for_tpu(True)     # the chip's grouped matmul and attention kernels
    cfg, config = _longcat_share()
    engine, stated = config["engine"], config["compiled_bytes_per_device"]
    cap, lanes = engine["cache_buckets"][-1], engine["lane_buckets"][-1]
    b, tc = (lanes, 1) if form == "decode" else (1, engine["prefill_token_buckets"][-1])
    assert (lanes, cap) == (16, 8192) and stated[form]["shape"] == [b, tc, cap]
    compiled = _kimi_extend_at(shaped, cfg, engine, b, tc, cap)
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_extend_{form}_{b}x{tc}x{cap},")
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    experts = [line for line in kernels if "extend.moe.experts" in line]
    assert len(experts) == 2 and not any("extend.moe.zero" in line for line in kernels)
    if form == "prefill":
        attends = [line for line in kernels if line not in experts]
        assert len(attends) == 2 and all(
            "/extend.attention/latent_attention/" in line for line in attends)

        def over_cache(types):
            return max((
                math.prod(map(int, dims.split(",")))
                for dims in re.findall(rf"(?:{types})\[([0-9,]+)\]", text)
                if str(cap) in dims.split(",")), default=0)

        assert over_cache("f32") < cfg.num_heads * layers.QUERY_BLOCK * cap
        assert over_cache("f32|bf16") < cap * cfg.num_heads * cfg.v_dim
    else:
        assert _attends_through_the_table(text, cfg, engine, sites=2) == experts
        assert f"f32[{lanes},{cfg.num_heads},1,{cap}]" not in text
        assert not re.search(rf"bf16\[(\d+,)*{cap},(\d+,)*{cfg.row_dim}\]", text)
    memory = compiled.memory_analysis()
    per_token = 2 * cfg.cache_layers * sum(h * d for h, d in cfg.cache_arrays)
    assert per_token == 8 * 1280
    pool = per_token * engine["num_blocks"] * engine["block_size"]
    handed = pool if form == "decode" else per_token * b * cap
    weights = memory.argument_size_in_bytes - handed
    assert 10.34e9 < weights < 10.36e9 and abs(weights - 2 * cfg.num_params()) < 2**20
    assert memory.argument_size_in_bytes == stated[form]["argument"]
    assert _holds_no_more_than_stated(memory, stated[form])
    assert memory.temp_size_in_bytes < (2**23 if form == "decode" else 0.4e9)
    # beside the pool (a decode call's arguments hold it) and the caches of a chunk in flight
    assert _device_bytes(compiled) + (0 if form == "decode" else pool) + (
        per_token * engine["prefill_lanes"] * cap) < HBM_BYTES
    # the fullest device holds what a deployment would: weights and pool past 60 %
    assert 2 * cfg.num_params() + pool > 0.70 * HBM_BYTES


@pytest.mark.parametrize(
    "name,extends,pagings",
    [("gptj-6b-serve", 12, 11), ("command-a-plus-serve-ep8", 20, 25),
     ("keye-vl2-30b-a3b-serve", 16, 19), ("kimi-k2-instruct-serve-ep32", 16, 11),
     ("granite-4.0-h-micro-serve", 20, 13), ("granite-4.0-h-small-serve-ep2", 20, 13),
     ("minicpm-sala-serve-pp2", 16, 19), ("mimo-v2-flash-serve-ep16", 20, 13),
     ("qwen3-next-80b-a3b-serve-ep4", 18, 14), ("glm-5-serve-ep16", 16, 19),
     ("longcat-flash-chat-serve-ep32", 18, 14)],
)
def test_a_serve_configuration_compiles_no_more_programs_than_it_did(name, extends, pagings):
    """The programs an engine with the configuration's buckets compiles (a tiny
    model: the count is the buckets'), against the count before a call's
    operands went up as one buffer (PR 28): built, it has compiled one gather
    per (lanes, cache), one page-back per (lanes, tokens) and the clone;
    ``warm()`` compiles one ``extend`` per shape of ``extend_shapes()``
    (``tests/test_llm.py`` holds it to that). ``setup_s`` is mostly these
    compiles. A model whose decode call reads pages (``llm.reads_pages``: MiMo,
    Qwen3-Next, both granites, since PR 66 Kimi K2, LongCat-Flash) compiles a gather for its
    chunks' one lane alone: twelve programs fewer than the 25 and 26 they had (PR 61;
    Kimi's eight ``gather_{2,4}x<cap>`` fewer than 19), and no ``gather_<b>x<cap>``
    that only a decode call would have used."""
    import json

    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs", name + ".json")) as f:
        sizes = json.load(f)["engine"]
    context = max(sizes["cache_buckets"])
    cfg = (
        cohere2_moe.cohere2_moe_nano(max_seq_len=context)
        if name.startswith("command-a-plus")
        else keye_vl2.keye_vl2_nano(max_seq_len=context) if name.startswith("keye")
        else kimi_k2.kimi_k2_nano(max_seq_len=context) if name.startswith("kimi")
        else glm_moe_dsa.glm_moe_dsa_nano(max_seq_len=context) if name.startswith("glm")
        else longcat_flash.longcat_flash_nano(max_seq_len=context) if name.startswith("longcat")
        else granitemoehybrid.granite_hybrid_nano(
            max_seq_len=context, ssm_chunk=256, router_experts=8 * name.count("small"))
        if name.startswith("granite")
        else minicpm_sala.minicpm_sala_nano(max_seq_len=context, linear_chunk=256)
        if name.startswith("minicpm")
        else mimo_v2_flash.mimo_v2_flash_nano(max_seq_len=context) if name.startswith("mimo")
        else qwen3_next.qwen3_next_nano(max_seq_len=context, delta_chunk=64)
        if name.startswith("qwen3")
        else dataclasses.replace(gpt.gpt_nano(), max_seq_len=context)
    )
    llm._paging_programs.cache_clear()      # this engine's programs alone
    try:
        eng = llm.LLMEngine(cfg, **sizes)
        assert len(set(eng.extend_shapes())) == len(eng.extend_shapes()) <= extends
        programs = llm._paging_programs()
        assert sum(
            p._cache_size() for p in (programs.gather, programs.page_back, programs.clone)
        ) <= pagings
        gathers = {f"gather_{b}x{cap}" for b, tc, cap in eng.extend_shapes() if not eng._paged(tc)}
        assert set(programs.gather.names()) == gathers
        adopts = name.split("-")[0] in ("granite", "mimo", "qwen3", "kimi", "longcat")
        assert llm.reads_pages(eng._extend) == adopts
        assert (gathers == {f"gather_1x{cap}" for cap in sizes["cache_buckets"]}) == adopts
    finally:
        llm._paging_programs.cache_clear()
