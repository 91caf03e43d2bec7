"""SmallThinker (``tiny-smallthinker``: of every four layers one full causal
layer WITHOUT a position embedding and three 16-token window layers with
rope; heads wider than the hidden size divides into; 8 ReGLU experts, 2 a
token, routed from the layer's input norm) against the plain reference
``tests/reference/smallthinker_decoder.py``, on seeded random weights, in
float32 on the CPU: the training model's logits, and the serving engine's
chunked prefill then decode through BOTH caches — the global layers' table
that grows and the window layers' ring, wrapped more than twice — in all
three program forms. Logits, never tokens. And the allocator a kind of
layer, against a naive model.

TOLERANCE. Everything here computes in float32 and the CPU's float32
matmul is exact to rounding, so program and reference differ by summation
order only: measured 3e-7 on logits of magnitude ~0.6 (the training model)
and 1e-6 (the engine). ``ATOL = 2e-4`` leaves two orders of magnitude for
another BLAS and is far below what bfloat16 compute does to the same
logits (``test_bf16_compute_fails_the_tolerance`` holds that end).
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REF_PATH = os.path.join(HERE, "reference", "smallthinker_decoder.py")
ATOL = 2e-4


def _load(path):
    spec = importlib.util.spec_from_file_location("smallthinker_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH)


def build(preset_over=None, **moe_overrides):
    """tiny-smallthinker in float32 with seeded weights."""
    from deepspeed_tpu.models import build_model, get_model_config
    from deepspeed_tpu.runtime.zero.planner import unbox_params

    base = get_model_config("tiny-smallthinker")
    # capacity form: room for every routed row, so that nothing is dropped
    moe = dataclasses.replace(base.moe, capacity_factor=8.0,
                              eval_capacity_factor=8.0, **moe_overrides)
    model = build_model("tiny-smallthinker", dtype=jnp.float32,
                        attn_impl="xla", moe=moe, **(preset_over or {}))
    tokens = np.random.default_rng(0).integers(0, 256, (1, 64)).astype(
        np.int32)
    params = unbox_params(model.init(jax.random.PRNGKey(3), tokens)["params"])
    return model, params, tokens


def reference_logits(model, params, row, rows=None, **kw):
    m = model.config
    return ref.forward_logits(
        row, embed=params["embed"],
        layer=lambda i: ref.program_layer(params, i),
        kinds=ref.program_kinds(m), window=m.sliding_window,
        ln_final=params["ln_final"]["scale"], unembed=params["unembed"],
        theta=float(m.rope_theta), eps=float(m.norm_eps),
        top_k=m.moe.top_k, rows=rows, q_block=16, **kw)


@pytest.fixture(scope="module")
def tiny():
    return build()


# ---------------------------------------------------------------------------
# the training model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dropless", [False, True],
                         ids=["capacity", "dropless"])
def test_transformer_lm_logits_match_the_reference(dropless):
    model, params, tokens = build(dropless=dropless, dropless_block_m=8)
    want = np.asarray(reference_logits(model, params, tokens[0]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, tokens))[0]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_bf16_training_forward_fails_the_tolerance(tiny):
    from deepspeed_tpu.models import build_model

    model, params, tokens = tiny
    low = build_model("tiny-smallthinker", dtype=jnp.bfloat16,
                      attn_impl="xla", moe=model.config.moe)
    want = np.asarray(reference_logits(model, params, tokens[0]))
    got = np.asarray(low.apply({"params": params}, tokens), np.float32)[0]
    assert np.abs(got - want).max() > 10 * ATOL


def test_the_router_reads_the_input_norm(tiny):
    """Routed from what the experts read (every earlier preset) the model
    leaves the reference: the published router sits before attention."""
    model, params, tokens = tiny
    moved, _, _ = build(router_input="ffn")
    want = np.asarray(reference_logits(model, params, tokens[0]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(moved.apply({"params": params}, tokens))[0]
    assert np.abs(got - want).max() > 10 * ATOL


@pytest.mark.parametrize("kind, moves", [("full_nope", False),
                                         ("window", True)])
def test_only_window_layers_read_positions(kind, moves):
    """A global layer carries no position embedding: its logits do not
    change when the positions are stretched (every gap doubled — a uniform
    shift would leave rope's relative angles alone too); a window layer's
    do. Program and reference both."""
    model, params, tokens = build(dict(layer_kinds=(kind,), num_layers=2))
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        a = np.asarray(model.apply({"params": params}, tokens, positions=pos))
        b = np.asarray(model.apply({"params": params}, tokens,
                                   positions=2 * pos))
    assert (np.abs(a - b).max() > 10 * ATOL) == moves
    if not moves:
        np.testing.assert_array_equal(a, b)
        ra = np.asarray(reference_logits(model, params, tokens[0]))
        rb = np.asarray(reference_logits(model, params, tokens[0],
                                         positions=2 * pos[0]))
        np.testing.assert_array_equal(ra, rb)


def test_the_benchmark_holds_the_same_reference():
    with open(REF_PATH, "rb") as a, open(os.path.join(
            ROOT, "benchmark", "reference", "smallthinker_decoder.py"),
            "rb") as b:
        assert a.read() == b.read()


def test_preset_holds_the_published_sizes():
    from deepspeed_tpu.models import get_model_config

    m = get_model_config("smallthinker-21b-a3b")
    assert (m.num_layers, m.hidden_size, m.num_heads, m.kv_heads,
            m.head_dim, m.ffn_size, m.vocab_size, m.max_seq_len) == \
        (52, 2560, 28, 4, 128, 768, 151936, 16384)
    assert m.num_heads * m.head_dim != m.hidden_size
    assert (m.moe.num_experts, m.moe.top_k, m.moe.normalize_gates,
            m.moe.router_input, m.moe.shared_expert_intermediate) == \
        (64, 6, True, "attn", None)
    assert (m.norm, m.norm_eps, m.rope_theta, m.tie_embeddings,
            m.activation, m.sliding_window) == \
        ("rmsnorm", 1e-6, 1.5e6, False, "relu_glu", 4096)
    # sliding_window_layout / rope_layout [0, 1, 1, 1] x 13
    assert [m.layer_kind(i) for i in range(8)] == \
        ["full_nope", "window", "window", "window"] * 2
    # 21.5 B parameters
    assert abs(m.num_params() - 21.5e9) < 0.1e9
    # every preset before it keeps its derived head width
    assert get_model_config("mistral-7b").head_dim == 128
    assert get_model_config("tiny-llama").kinds_period == ("full",)
    assert get_model_config("mistral-7b").kinds_period == ("window",)


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------

#: ring = ceil((16 + 16) / 8) + 1 = 5 blocks = 40 tokens a sequence
ENGINE = {"block_size": 8, "num_blocks": 64, "max_seqs": 2, "chunk": 16,
          "max_seq_len": 256, "decode_window": 1}
PROMPT_LEN = 125         # three rings: every slot overwritten twice over


def serve_logits(model, params, prompt, dtype, n_step=4, n_window=4):
    """Drive ``InferenceEngineV2`` by its own plans: chunked prefill, then
    ``n_step`` single decode steps, then ``n_window`` iterations in the
    decode window's form (fresh K/V staged beside the read-only pools).
    Returns {form: [(tokens so far, logits row)]}, teacher-forced on the
    engine's own argmax."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.forward import merge_step

    # (a copy: the engine donates the per-layer leaves to their stack)
    eng = InferenceEngineV2(model, params=jax.tree.map(jnp.copy, params),
                            config={**ENGINE, "dtype": dtype},
                            rng=jax.random.PRNGKey(0))
    assert "layers_stacked" in eng.params          # the scanned walk
    assert [k.name for k in eng._kinds] == ["full", "window"]
    eng.put(1, prompt, max_new_tokens=n_step + n_window + 2)
    def step(params, pools, slot_maps, tok, pos, tables, lens, sample_idx):
        # a step program less its sampling: the forward, then the ONE pool
        # write
        (k_ys, v_ys), logits = eng._forward(params, pools, tok, pos, tables,
                                            lens, sample_idx)
        return merge_step(pools, slot_maps, k_ys, v_ys, tok.shape[1]), logits

    fwd = jax.jit(step)
    out = {"prefill": [], "step": [], "window": []}
    seq = eng.state.seqs[1]
    chunks = 0
    while len(out["step"]) < n_step:
        plan = eng.scheduler.next_step()
        slots, tables = plan.more["window"]
        args = [(jnp.asarray(plan.slot_map), jnp.asarray(slots)),
                jnp.asarray(plan.token_ids), jnp.asarray(plan.positions),
                (jnp.asarray(plan.block_tables), jnp.asarray(tables)),
                jnp.asarray(plan.seq_lens), jnp.asarray(plan.sample_idx)]
        eng.kv_pool, logits = fwd(eng.params, eng.kv_pool, *args)
        chunks += plan.kind == "prefill"
        sampled = {}
        if plan.do_sample[0]:
            row = np.asarray(logits, np.float32)[0]
            out["prefill" if plan.kind == "prefill" else "step"].append(
                (list(seq.tokens), row))
            sampled = {1: int(np.argmax(row))}
        eng.scheduler.mark_dispatched(plan)
        eng.scheduler.commit(plan, sampled)
    assert chunks >= 7                              # the prompt came in chunks
    # the window form, as ``_window_program``'s ``_iter`` calls it
    m, cfg = model.config, eng.config
    S, Ws = cfg.max_seqs, 8
    tables = []
    for k in eng._kinds:
        t = np.zeros((S, k.max_blocks), np.int32)
        blocks = eng.state.blocks_of(seq, k.name)
        t[seq.slot, :len(blocks)] = blocks
        tables.append(jnp.asarray(t))
    tables = tuple(tables)
    kbuf = vbuf = tuple(
        jnp.zeros((len(k.layers), S, m.kv_heads, Ws, m.head_dim), dtype)
        for k in eng._kinds)
    toks = list(seq.tokens)
    base = np.zeros(S, np.int32)
    base[seq.slot] = len(toks) - 1
    win = jax.jit(lambda p, pool, tok, pos, lens, kb, vb, i, b:
                  eng._forward(p, pool, tok, pos, tables, lens,
                               jnp.zeros_like(lens), kv_stage=(kb, vb),
                               stage_fill=i, stage_starts=b))
    for i in range(n_window):
        tok = np.zeros(S, np.int32)
        pos = np.zeros(S, np.int32)
        lens = np.zeros(S, np.int32)
        tok[seq.slot], pos[seq.slot], lens[seq.slot] = \
            toks[-1], len(toks) - 1, len(toks)
        (kbuf, vbuf), logits = win(
            eng.params, eng.kv_pool, jnp.asarray(tok)[:, None],
            jnp.asarray(pos)[:, None], jnp.asarray(lens), kbuf, vbuf,
            jnp.int32(i), jnp.asarray(base))
        row = np.asarray(logits, np.float32)[seq.slot]
        out["window"].append((list(toks), row))
        toks.append(int(np.argmax(row)))
    out["ring_blocks_reused"] = eng.state.kinds["window"].blocks_reused
    return out


@pytest.fixture(scope="module")
def served(tiny):
    model, params, _ = tiny
    prompt = np.random.default_rng(2).integers(0, 256, PROMPT_LEN).tolist()
    return serve_logits(model, params, prompt, jnp.float32)


@pytest.mark.parametrize("form", ["prefill", "step", "window"])
def test_serving_matches_the_reference(tiny, served, form):
    """Prefill in chunks, then decode through both caches, against the
    reference's FULL forward over the same tokens — at contexts of two
    rings and more, where every window layer reads wrapped slots."""
    model, params, _ = tiny
    assert served[form]
    assert served["ring_blocks_reused"] >= 2 * 5     # wrapped twice over
    for toks, row in served[form]:
        assert len(toks) > 2 * 40
        want = np.asarray(reference_logits(
            model, params, np.asarray(toks, np.int32),
            rows=[len(toks) - 1]))[0]
        np.testing.assert_allclose(row, want, atol=ATOL, rtol=0,
                                   err_msg=f"{form} at {len(toks)} tokens")


def test_bf16_compute_fails_the_tolerance(tiny):
    """The tolerance is tight enough to tell precisions apart: the same
    engine computing in bfloat16 leaves it by more than an order."""
    model, params, _ = tiny
    prompt = np.random.default_rng(2).integers(0, 256, PROMPT_LEN).tolist()
    low = serve_logits(model, params, prompt, jnp.bfloat16, n_step=1,
                       n_window=1)
    worst = 0.0
    for form in ("prefill", "step", "window"):
        for toks, row in low[form]:
            want = np.asarray(reference_logits(
                model, params, np.asarray(toks, np.int32),
                rows=[len(toks) - 1]))[0]
            worst = max(worst, float(np.abs(row - want).max()))
    assert worst > 10 * ATOL


@pytest.fixture(scope="module")
def kernel_engine():
    """The engine's own programs (decode windows of 4, the Pallas paged
    kernel interpreted: heads of 64) after two requests, one of them past
    two rings. (engine, prompts, streams)."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    model = build_model("tiny-smallthinker", dtype=jnp.float32, head_size=64)
    eng = InferenceEngineV2(
        model, rng=jax.random.PRNGKey(0),
        config={**ENGINE, "max_seqs": 4, "decode_window": 4,
                "dtype": jnp.float32})
    assert eng._attn_decode_sel.is_pallas
    rng = np.random.default_rng(0)
    prompts = {1: rng.integers(0, 256, 70).tolist(),
               2: rng.integers(0, 256, 9).tolist()}
    eng.put(1, prompts[1], max_new_tokens=24)
    eng.put(2, prompts[2], max_new_tokens=17)
    out: dict = {1: [], 2: []}
    for _ in range(400):
        for uid, toks in eng.step().items():
            out[uid].extend(toks)
        if all(eng.query(u)["done"] for u in out):
            break
    return eng, prompts, out


def test_the_engines_own_programs_serve_the_reference_argmax(kernel_engine):
    """put/step end to end — SplitFuse plans, the decode window program
    with a pool and a table a kind, the paged kernel over ring and table:
    every served token is the float32 reference's argmax."""
    eng, prompts, out = kernel_engine
    assert {u: len(t) for u, t in out.items()} == {1: 24, 2: 17}
    assert eng.stats["windows"] > 0 and eng.stats["attn_gather_decode"] == 0
    for uid, served_toks in out.items():
        toks = np.asarray(prompts[uid] + served_toks, np.int32)
        P, n = len(prompts[uid]), len(served_toks)
        logits = np.asarray(reference_logits(
            eng.model, eng.params, toks, rows=np.arange(P - 1, P - 1 + n)))
        margin = logits.max(axis=1) - logits[np.arange(n), served_toks]
        assert margin.max() <= ATOL, (uid, margin)


def test_counters_by_kind_follow_the_plans(kernel_engine):
    eng, _, _ = kernel_engine
    st = eng.stats
    # blocks are reserved at admission: 94 and 26 tokens of 8-token blocks
    # in the global layers' table, a whole ring (5) and 4 in the window's
    assert st["kv_blocks_peak_full"] == 12 + 4
    assert st["kv_blocks_peak_window"] == 5 + 4
    assert st["ring_blocks_reused"] > 0
    for name in ("attn_steps_live", "attn_steps_rect"):
        assert st[name] == st[f"{name}_full"] + st[f"{name}_window"] > 0
    # the long request is past the window: pages a growing table would
    # have walked and the ring did not
    assert 0 < st["attn_pages_clipped"] < st["attn_pages_unclipped"]


def test_window_and_global_cores_are_told_apart_inside_attn_core(
        kernel_engine):
    from deepspeed_tpu.profiling import trace as ptrace

    eng, _, _ = kernel_engine
    seen = set()
    for prog in eng._programs.values():
        if getattr(prog, "avals", None) is None:
            continue
        for op in prog.scopes()["ops"].values():
            sub = ptrace.sub_scope_of(op)
            if sub:
                assert ptrace.scope_of(op)[0] == "attn_core", op
                seen.add(sub)
    assert seen == {"attn_full", "attn_window"}
    assert ptrace.sub_scope_of("jit(run)/layer/attn_core/dot") is None


def test_a_window_layer_on_a_table_equals_the_same_layer_on_its_ring():
    """The paged kernel over a table that holds every page, under the
    window bound, against the same keys and values in a ring of 5 slots:
    the same pages reach the same online softmax, so the outputs agree —
    bit for bit where the ring's slot order is the position order (the
    newest page in the last slot), to rounding where the ring has turned
    (the softmax then meets the pages in another order)."""
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_ragged_attention

    bs, W, D, KV, H, nring = 8, 16, 64, 2, 4, 5
    rng = np.random.default_rng(4)
    for n_ctx, exact in ((nring * bs - 3, True), (87, False)):
        n_pages = -(-n_ctx // bs)
        keys = rng.standard_normal((2, KV, n_pages * bs, D)).astype(
            np.float32)
        table_pool = np.zeros((1, 2, KV, n_pages + 1, bs, D), np.float32)
        ring_pool = np.zeros((1, 2, KV, nring + 1, bs, D), np.float32)
        for pg in range(n_pages):
            page = keys[:, :, pg * bs:(pg + 1) * bs]
            table_pool[0, :, :, pg + 1] = page
            ring_pool[0, :, :, pg % nring + 1] = page   # the newest stays
        q = jnp.asarray(rng.standard_normal((1, 1, H, D)), jnp.float32)
        stage = jnp.asarray(rng.standard_normal((2, 1, KV, 8, D)),
                            jnp.float32)
        lens = jnp.asarray([n_ctx + 1], jnp.int32)
        start = jnp.asarray([n_ctx], jnp.int32)
        common = dict(block_size=bs, layer_index=jnp.int32(0), window=W)
        on_table = paged_ragged_attention(
            q, jnp.asarray(table_pool), stage[0], stage[1],
            jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None], lens, start,
            start, **common)
        on_ring = paged_ragged_attention(
            q, jnp.asarray(ring_pool), stage[0], stage[1],
            jnp.arange(1, nring + 1, dtype=jnp.int32)[None], lens, start,
            start, ring_tokens=nring * bs, **common)
        if exact:
            np.testing.assert_array_equal(np.asarray(on_table),
                                          np.asarray(on_ring))
        else:
            np.testing.assert_allclose(np.asarray(on_table),
                                       np.asarray(on_ring), atol=1e-6)


@pytest.mark.parametrize("preset, over, want", [
    ("tiny-llama", {}, [("full", 32, 0)]),
    ("tiny-llama", {"sliding_window": 16}, [("window", 5, 40)]),
    ("tiny-smallthinker", {}, [("full", 32, 0), ("window", 5, 40)]),
], ids=["full", "all_window", "both"])
def test_one_cache_a_kind_of_layer(preset, over, want):
    """A model of one kind has one allocator and one pool — the all-window
    model's IS the ring; ring = ceil((W + max(chunk, decode window)) /
    block) + 1; a further kind's pool is every slot's whole ring."""
    from deepspeed_tpu.inference.engine_v2 import (RaggedInferenceConfig,
                                                   cache_kinds)
    from deepspeed_tpu.models import get_model_config

    cfg = RaggedInferenceConfig(**{**ENGINE, "max_seqs": 3})
    kinds = cache_kinds(get_model_config(preset, **over), cfg)
    assert [(k.name, k.max_blocks, k.ring_tokens) for k in kinds] == want
    assert kinds[0].num_blocks == 64
    assert [k.num_blocks for k in kinds[1:]] == [3 * 5 + 1] * (len(kinds) - 1)
    assert sorted(i for k in kinds for i in k.layers) == list(range(
        get_model_config(preset, **over).num_layers))


def test_a_model_that_keeps_a_ring_packs_rows_only():
    """Plans carry exactly the rows that have work, ``chunk`` tokens each:
    the ring is sized for chunk-at-most steps, so the chunk never grows;
    what a ring cannot do is refused with the reason ring mode gave."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    model = build_model("tiny-smallthinker")
    eng = InferenceEngineV2(model, rng=jax.random.PRNGKey(0),
                            config={**ENGINE, "max_seqs": 3})
    assert "rolling ring" in eng.state.not_a_page_chain
    assert eng._prefix_cache is None
    assert eng.scheduler.program_shape_menu() == [(16, 1), (16, 2), (16, 3)]
    eng.put(1, list(range(1, 40)), max_new_tokens=4)
    plan = eng.scheduler.next_step()
    assert plan.token_ids.shape == (1, 16)
    assert plan.more["window"][0].shape == (1, 16)
    assert plan.more["window"][1].shape == (1, 5)
    with pytest.raises(ValueError, match="rolling KV ring"):
        InferenceEngineV2(model, rng=jax.random.PRNGKey(0),
                          config={**ENGINE, "prefix_cache": True})
    with pytest.raises(ValueError, match="rolling"):
        InferenceEngineV2(model, rng=jax.random.PRNGKey(0),
                          config={**ENGINE, "spec_decode": "ngram"})


# ---------------------------------------------------------------------------
# the allocator a kind of layer, against a naive model
# ---------------------------------------------------------------------------

def _state(ring_pool=11):
    from deepspeed_tpu.inference.ragged import StateManager

    return StateManager(40, 8, 4, 32, kind="full",
                        more_kinds={"window": (ring_pool, 5, True)})


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_allocators_against_a_naive_model(seed):
    """Random admissions and releases: a naive count of what each kind must
    hold — ceil(tokens / block) blocks in the table that grows, at most the
    ring in the window kind — agrees with both allocators at every step; a
    refused admission changes NOTHING in either; the last release returns
    every block of every kind."""
    st = _state()
    rng = np.random.default_rng(seed)
    live: dict[int, int] = {}
    uid = refused = 0
    for _ in range(300):
        if live and rng.random() < 0.45:
            gone = int(rng.choice(list(live)))
            st.release(gone)
            del live[gone]
        else:
            uid += 1
            prompt, new = int(rng.integers(1, 150)), int(rng.integers(1, 60))
            need = (-(-(prompt + new) // 8), min(-(-(prompt + new) // 8), 5))
            free = (st.kinds["full"].allocator.free_blocks,
                    st.kinds["window"].allocator.free_blocks)
            fits = len(live) < 4 and free[0] >= need[0] and free[1] >= need[1]
            assert st.can_admit(prompt, new) == fits
            if fits:
                seq = st.admit(uid, list(range(prompt)), new)
                assert len(seq.blocks) == need[0]
                assert len(seq.kind_blocks["window"]) == need[1] <= 5
                live[uid] = prompt + new
            else:
                refused += 1
                with pytest.raises(RuntimeError):
                    st.admit(uid, list(range(prompt)), new)
                # refused whole: neither kind is left half-reserved
                assert free == (st.kinds["full"].allocator.free_blocks,
                                st.kinds["window"].allocator.free_blocks)
                assert uid not in st.seqs
        assert st.kinds["full"].allocator.free_blocks == 39 - sum(
            -(-n // 8) for n in live.values())
        assert st.kinds["window"].allocator.free_blocks == 10 - sum(
            min(-(-n // 8), 5) for n in live.values())
        st.audit()
    assert refused > 0
    for gone in list(live):
        st.release(gone)
    assert st.kinds["full"].allocator.free_blocks == 39
    assert st.kinds["window"].allocator.free_blocks == 10
    assert sorted(st._free_slots) == [0, 1, 2, 3]


def test_a_window_kind_that_is_full_refuses_the_whole_admission():
    """Room in the growing table, none in the ring pool: the admission is
    refused and the table's blocks go back."""
    st = _state(ring_pool=8)                  # 7 usable: one ring and a bit
    st.admit(1, list(range(60)), 4)           # takes a whole ring (5)
    assert not st.can_admit(60, 4)
    before = st.allocator.free_blocks
    with pytest.raises(RuntimeError, match="KV pool exhausted"):
        st.admit(2, list(range(60)), 4)
    assert st.allocator.free_blocks == before
    assert st.kinds["window"].allocator.free_blocks == 2
    assert st.can_admit(10, 4)                # two blocks still fit
    st.admit(3, list(range(10)), 4)
    st.audit()


def test_ring_slots_overwritten_are_counted():
    st = _state()
    seq = st.admit(1, list(range(100)), 20)
    st.note_written(seq, 0, 40)               # fills the ring: nothing reused
    assert st.kinds["window"].blocks_reused == 0
    st.note_written(seq, 40, 56)              # pages 5 and 6 take old slots
    assert st.kinds["window"].blocks_reused == 2
    st.note_written(seq, 56, 57)              # page 7 starts
    st.note_written(seq, 57, 58)              # ...and goes on: counted once
    assert st.kinds["window"].blocks_reused == 3
    assert st.sample() == {"full": 15, "window": 5}
    assert st.kinds["full"].blocks_peak == 15
