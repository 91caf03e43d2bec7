"""What a chain of pages can do stays allowed on the LATENT page
(``tiny-kanana2``: ONE row ``[c | k_r]`` a token a layer, no K/V halves) —
chunk growth, a prefix-trie hit, rewind, pages moved between engines — and
what this kind does not serve yet is refused by its name. The model, its
reference and the serving forms are ``tests/test_kanana2.py``'s."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_kanana2 import build, hold_to_the_reference
from tests.test_lfm2_moe import CHUNK, Tap, _prompt, serve


@pytest.fixture(scope="module")
def tiny():
    return build()


def test_a_lone_prompts_chunk_grows_along_the_chain(tiny, monkeypatch):
    """The latent kind is a linear chain of pages: a lone prompt's chunk
    GROWS along the scheduler's chain (``prefill_grow_chunk`` False, which
    packs rows only, is held in ``tests/test_kanana2.py``'s riding test)
    and the grown plans serve the reference's logits."""
    model, params, _ = tiny
    rng = np.random.default_rng(6)
    requests = {1: (_prompt(rng, 7 * CHUNK + 3), 5)}
    tap = Tap(monkeypatch)
    eng, out = serve(model, params, tap, requests, max_seq_len=256)
    assert eng.scheduler.grow_chunk
    widths = {e["plan"].token_ids.shape[1] for e in tap.entries
              if e["kind"] == "plan" and e["plan"].kind == "prefill"}
    assert max(widths) > CHUNK
    hold_to_the_reference(model, params, tap, requests, out)


def test_a_prefix_trie_hit_equals_a_cold_run_and_rewind_works(
        tiny, monkeypatch):
    """Two requests share 40 tokens: the second is served from the trie's
    published latent pages (prefix_hit_tokens > 0) and its logits equal the
    reference's cold forward; a speculative-style rewind is allowed on the
    chain."""
    model, params, _ = tiny
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(8)
    shared = _prompt(rng, 40)
    requests = {1: (shared + _prompt(rng, 5), 6),
                2: (shared + _prompt(rng, 9), 6)}
    eng, out = serve(model, params, tap, requests, arrivals={2: 30},
                     prefix_cache=True)
    assert eng.stats["prefix_hit_tokens"] >= 32
    hold_to_the_reference(model, params, tap, requests, out)
    eng.state.audit()
    eng.put(7, shared, max_new_tokens=4)
    while not eng.query(7)["done"]:
        eng.step()
    seq = eng.state.seqs[7]
    eng.state.rewind(7, seq.tokens[:len(shared) + 2])      # not refused
    eng.flush(7)
    eng.state.audit()


def test_pages_move_between_engines_with_the_latent_row(tiny):
    """What ships pages elsewhere carries the latent page as it is (a page
    is ``[layers, 1, 1, block, lanes]``): a sequence exported mid-stream
    and imported by a second engine continues with the same tokens."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    model, params, _ = tiny
    conf = {"block_size": 8, "num_blocks": 48, "max_seqs": 2, "chunk": 16,
            "max_seq_len": 128, "dtype": jnp.float32, "decode_window": 1}
    mk = lambda: InferenceEngineV2(
        model, params=jax.tree.map(jnp.copy, params), config=conf,
        rng=jax.random.PRNGKey(0))
    prompt = _prompt(np.random.default_rng(9), 37)
    (want,) = mk().generate([prompt], max_new_tokens=40)
    a, b = mk(), mk()
    assert a._page_shape == (3, 1, 1, 8, 128)
    a.put(1, prompt, max_new_tokens=40)
    got = []
    while len(got) < 5:
        got += a.step().get(1, [])
    bundle = a.export_migration(1)
    b.import_reserve(1, bundle.meta())
    b.import_complete(1, bundle)
    a.export_commit(1)
    got = list(bundle.tokens[len(prompt):])
    while not b.query(1)["done"]:
        got += b.step().get(1, [])
    assert got == want
    a.state.audit()
    b.state.audit()


def test_a_uniform_latent_stack_is_scanned(monkeypatch):
    """A stack whose layers are all alike (every layer routed experts) is
    walked by ``scan_layers`` over the one latent kind — the stacked
    experts closed over, the staged rows without a V half — and serves the
    reference's logits through prefill chunks and decode windows."""
    model, params, _ = build(num_layers=2, moe=dataclasses.replace(
        build()[0].config.moe, moe_layer_pattern=(True, True)))
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(11)
    requests = {1: (_prompt(rng, 2 * CHUNK + 5), 10), 2: (_prompt(rng, 3), 9)}
    eng, out = serve(model, params, tap, requests, decode_window=8)
    assert "layers_stacked" in eng.params and eng.stats["windows"] > 0
    hold_to_the_reference(model, params, tap, requests, out)


def test_a_published_prefix_is_pulled_with_the_latent_row(tiny):
    """A cross-replica radix pull: engine A's published chain exported as a
    prefix bundle and adopted by engine B's trie; B then serves the same
    prompt from the pulled latent pages with A's tokens."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    model, params, _ = tiny
    conf = {"block_size": 8, "num_blocks": 48, "max_seqs": 2, "chunk": 16,
            "max_seq_len": 128, "dtype": jnp.float32, "prefix_cache": True}
    mk = lambda: InferenceEngineV2(
        model, params=jax.tree.map(jnp.copy, params), config=conf,
        rng=jax.random.PRNGKey(0))
    prompt = _prompt(np.random.default_rng(10), 43)
    a, b = mk(), mk()
    (want,) = a.generate([prompt], max_new_tokens=6)
    bundle = a.export_prefix(prompt)
    assert b.import_prefix(bundle) == 5                   # whole pages
    (got,) = b.generate([prompt], max_new_tokens=6)
    assert got == want and b.stats["prefix_hit_tokens"] == 40
    a.state.audit()
    b.state.audit()


@pytest.mark.parametrize("over, text", [
    ({"spec_decode": "ngram"}, "does not serve under spec_decode"),
    ({"kv_cache_dtype": "fp8"}, "does not serve under kv_cache_dtype"),
    ({"kv_tier": True, "prefix_cache": True},
     "does not serve under kv_tier"),
])
def test_what_the_latent_kind_does_not_serve_is_refused_by_name(tiny, over,
                                                                text):
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    model, params, _ = tiny
    with pytest.raises(ValueError, match=f"kind 'latent'.*{text}"):
        InferenceEngineV2(model, params=params, config={
            "block_size": 8, "num_blocks": 16, "max_seqs": 2, "chunk": 16,
            "max_seq_len": 64, "dtype": jnp.float32, **over})


def test_v1_and_unlike_layers_are_refused_by_name(tiny):
    from deepspeed_tpu.inference.engine_v2 import RaggedInferenceConfig
    from deepspeed_tpu.inference.forward import cache_kinds
    from deepspeed_tpu.models.transformer import LatentAttention

    model, params, tokens = tiny
    cfg = model.config
    x = jnp.zeros((1, 4, cfg.hidden_size), jnp.float32)
    with pytest.raises(ValueError, match="no v1 kv_cache"):
        LatentAttention(cfg).apply(
            {"params": params["layer_0"]["attn"]}, x, jnp.arange(4)[None],
            kv_cache=(x, x, 0))
    with pytest.raises(ValueError, match="full causal rope layers only"):
        cache_kinds(dataclasses.replace(cfg, sliding_window=16),
                    RaggedInferenceConfig())


# ---------------------------------------------------------------------------
# tracing: the scope and the kernel names land in this model's programs and
# in no other's
# ---------------------------------------------------------------------------

def _kernels_of(prog) -> set:
    from tests.test_device_scopes import _kernel_names

    args, kwargs = prog.avals
    return _kernel_names(jax.make_jaxpr(prog.fn)(*args, **kwargs).jaxpr)


@pytest.mark.parametrize("preset, latent", [("tiny-kanana2", True),
                                            ("tiny-olmoe", False)])
def test_the_latent_scope_and_kernel_names_are_this_models(preset, latent):
    """``latent_absorb`` is in the prefill step, the decode step and the
    decode window of a latent model and in no other model's; the decode
    programs launch ``paged_latent_decode`` once a LAYER and nothing else,
    the prefill step the prefill form for its chunks and the decode form for
    the rows that ride it — what ``latent_attn_roofline`` matches by name."""
    from tests.test_lfm2_programs import MODULES, _programs_of

    found, kinds = _programs_of(preset)
    assert MODULES <= set(found)
    for mod in MODULES:
        scopes, pools = found[mod]
        assert (scopes[("latent_absorb", "fwd")] > 0) == latent, \
            (mod, dict(scopes))
        assert all([p.shape[1] for p in ps] == [1 if latent else 2]
                   for ps in pools)
    assert kinds[0].is_latent == latent


def test_the_decode_programs_launch_the_latent_kernel_once_a_layer(tiny):
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    model, params, _ = tiny
    eng = InferenceEngineV2(
        model, params=jax.tree.map(jnp.copy, params), config={
            "block_size": 8, "num_blocks": 64, "max_seqs": 2, "chunk": 8,
            "max_seq_len": 128, "dtype": jnp.float32})
    eng.generate([list(range(5, 24)), [3, 4]], max_new_tokens=6)
    for key, prog in eng._programs.items():
        names = _kernels_of(prog)
        if key[0] == "win" or key[0] == 1:
            assert names == {"paged_latent_decode", "grouped_matmul_fwd"}
        else:
            assert names == {"paged_latent_prefill", "paged_latent_decode",
                             "grouped_matmul_fwd"}
    # the kernel's steps are booked as the paged kernel's are
    assert eng.stats["attn_steps_live_latent"] == eng.stats[
        "attn_steps_live"] > 0


#: the four other served families: a tiny preset of each, and at the
#: PUBLISHED shape of its benchmark cell — (chunk, query heads, KV heads a
#: page row, lanes of a row), pages of 128, bf16 — what ``paged_plan``
#: returned on the parent of PR 60 (``5ed1c74``) for a decode row and for a
#: chunk, as ``(TG, KV, block_size, tqb)``
OTHER_KINDS = {
    "mistral": ("tiny-llama", {"sliding_window": 64}, (128, 32, 8, 128),
                (4, 8, 128, 4), (512, 8, 128, 512)),
    "olmoe": ("tiny-olmoe", {}, (128, 16, 16, 128),
              (1, 16, 128, 1), (128, 16, 128, 128)),
    "smallthinker": ("tiny-smallthinker", {}, (512, 28, 4, 128),
                     (7, 4, 128, 7), (3584, 4, 128, 896)),
    "lfm2": ("tiny-lfm2-moe", {}, (512, 32, 4, 128),
             (8, 4, 128, 8), (4096, 4, 128, 1024)),
}


@pytest.mark.parametrize("family", list(OTHER_KINDS))
def test_the_expanded_latent_form_is_invisible_to_the_other_kinds(
        family, monkeypatch):
    """The latent kind's prefill form (PR 60) is a kernel and a branch of
    its OWN: an engine of another kind never calls its plan or its entry
    (both raise here), its prefill step launches ``paged_attn_prefill`` for
    the chunks and ``paged_attn_decode`` for the rows that ride it (and the
    grouped GEMM where it has experts) and nothing else, and ``paged_plan``
    returns for its published shape what it returned on the parent — so a
    ``correct: false`` in one of their cells is not this change's."""
    import deepspeed_tpu.inference.engine_v2 as ev
    import deepspeed_tpu.inference.forward as fwd
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.ops.pallas.paged_attention import paged_plan

    preset, over, (chunk, H, KV, lanes), decode_plan, chunk_plan = \
        OTHER_KINDS[family]

    def never(*a, **k):
        raise AssertionError("the latent kind's code, reached from a "
                             f"{family} engine")

    monkeypatch.setattr(ev, "latent_prefill_plan", never)
    monkeypatch.setattr(fwd, "latent_prefill_plan", never)
    monkeypatch.setattr(fwd, "paged_latent_prefill", never)
    # (heads of 64, two a page row: a width the paged kernel serves)
    eng = ev.InferenceEngineV2(
        build_model(preset, head_size=64, **over), rng=jax.random.PRNGKey(6),
        config={"block_size": 8, "num_blocks": 64, "max_seqs": 2, "chunk": 8,
                "max_seq_len": 128})
    eng.generate([list(range(5, 24)), [3, 4]], max_new_tokens=6)
    assert eng._attn_paged and not any(k.is_latent for k in eng._kinds)
    steps = 0
    for key, prog in eng._programs.items():
        names = _kernels_of(prog) - {"grouped_matmul_fwd"}
        if key[0] == "win" or key[0] == 1:
            assert names == {"paged_attn_decode"}, (key, names)
        else:
            assert names == {"paged_attn_prefill", "paged_attn_decode"}, \
                (key, names)
            steps += 1
    assert steps
    G = H // KV
    assert tuple(paged_plan(G, KV, 128, jnp.bfloat16, lanes=lanes)) \
        == decode_plan
    assert tuple(paged_plan(chunk * G, KV, 128, jnp.bfloat16, lanes=lanes)) \
        == chunk_plan
