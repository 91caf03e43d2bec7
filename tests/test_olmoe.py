"""OLMoE (``tiny-olmoe``: q/k normalised as whole vectors, 8 experts, 2 a
token, gates not renormalised) against the plain reference
``tests/reference/olmoe_decoder.py``, on seeded random weights, in float32
on the CPU: the training model's logits and loss gradients, and the serving
engine's chunked prefill then decode through the paged cache in all three
program forms — logits, never tokens.

TOLERANCE. Everything here computes in float32 and the CPU's float32
matmul is exact to rounding, so program and reference differ by summation
order only: measured 2e-6 on logits of magnitude ~1 (gradients 1e-7).
``ATOL = 2e-4`` leaves two orders of magnitude for another BLAS and is
fifty times below what bfloat16 compute does to the same logits (1e-2 and
more — ``test_bf16_compute_fails_the_tolerance`` holds that end).
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
REF_PATH = os.path.join(HERE, "reference", "olmoe_decoder.py")
ATOL = 2e-4


def _load(path):
    spec = importlib.util.spec_from_file_location("olmoe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH)


def build(**moe_overrides):
    """tiny-olmoe in float32 with seeded weights and NON-TRIVIAL q/k norm
    scales (the initialiser's ones would hide a misplaced scale)."""
    from deepspeed_tpu.models import build_model, get_model_config
    from deepspeed_tpu.runtime.zero.planner import unbox_params

    base = get_model_config("tiny-olmoe")
    # capacity form: room for every routed row, so that nothing is dropped
    moe = dataclasses.replace(base.moe, capacity_factor=8.0,
                              eval_capacity_factor=8.0, **moe_overrides)
    model = build_model("tiny-olmoe", dtype=jnp.float32, attn_impl="xla",
                        moe=moe)
    tokens = np.random.default_rng(0).integers(0, 256, (1, 40)).astype(
        np.int32)
    params = unbox_params(model.init(jax.random.PRNGKey(3), tokens)["params"])
    rng = np.random.default_rng(5)
    for i in range(model.config.num_layers):
        a = params[f"layer_{i}"]["attn"]
        for k in ("q_norm", "k_norm"):
            a[k] = jnp.asarray(rng.uniform(0.5, 1.5, a[k].shape), jnp.float32)
    return model, params, tokens


def reference_logits(model, params, row, rows=None, **kw):
    m = model.config
    return ref.forward_logits(
        row, embed=params["embed"],
        layer=lambda i: ref.program_layer(params, i),
        num_layers=m.num_layers, ln_final=params["ln_final"]["scale"],
        unembed=params["unembed"], theta=float(m.rope_theta),
        eps=float(m.norm_eps), top_k=m.moe.top_k, rows=rows, **kw)


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
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, tokens))[0]
    want = np.asarray(reference_logits(model, params, tokens[0]))
    assert got.shape == want.shape == (40, 256)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_loss_gradients_match_the_reference(tiny):
    from deepspeed_tpu.models import cross_entropy_lm

    model, params, tokens = tiny
    labels = jnp.asarray(np.concatenate(
        [tokens[:, 1:], np.full((1, 1), -100)], axis=1))

    def loss_program(p):
        return cross_entropy_lm(model.apply({"params": p}, tokens), labels)

    def loss_reference(p):
        return ref.lm_loss(reference_logits(model, p, tokens[0]), tokens[0])

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(loss_program)(params)
        lr, gr = jax.value_and_grad(loss_reference)(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    flat_p = jax.tree_util.tree_leaves_with_path(gp)
    flat_r = dict(jax.tree_util.tree_leaves_with_path(gr))
    assert len(flat_p) == len(flat_r)
    for path, g in flat_p:
        # gradients are ~1e-2 and smaller: a tenth of the logits' tolerance
        np.testing.assert_allclose(np.asarray(g), np.asarray(flat_r[path]),
                                   atol=ATOL / 10, rtol=1e-3,
                                   err_msg=jax.tree_util.keystr(path))
    # the new leaves are live: q/k norm scales and every expert get gradient
    a = gp["layer_1"]["attn"]
    assert float(jnp.abs(a["q_norm"]).max()) > 0
    assert float(jnp.abs(a["k_norm"]).max()) > 0


def test_gates_are_not_renormalised(tiny):
    """``normalize_gates`` flipped (Mixtral's form) leaves the reference:
    the published model weights an expert by its softmax probability."""
    model, params, tokens = tiny
    flipped, _, _ = build(normalize_gates=True)
    want = np.asarray(reference_logits(model, params, tokens[0]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(flipped.apply({"params": params}, tokens))[0]
    assert np.abs(got - want).max() > 50 * ATOL
    # ...and it IS the reference's renormalised variant, so the difference
    # is the gates and nothing else
    renorm = np.asarray(reference_logits(model, params, tokens[0],
                                         renormalise=True))
    np.testing.assert_allclose(got, renorm, atol=ATOL, rtol=0)


def test_qk_norm_is_over_the_whole_vector():
    """One RMS over all heads together — the per-head form (a statistic a
    head) gives other numbers whenever heads differ in size."""
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models.transformer import qk_norm

    cfg = get_model_config("tiny-olmoe")
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 5, 4, 16)).astype(np.float32) \
        * np.asarray([0.1, 1.0, 3.0, 10.0], np.float32)[:, None]
    scale = rng.uniform(0.5, 1.5, (4, 16)).astype(np.float32)
    got = np.asarray(qk_norm(cfg, jnp.asarray(x), jnp.asarray(scale)))
    flat = x.reshape(2, 5, 64)
    whole = (flat / np.sqrt((flat ** 2).mean(-1, keepdims=True)
                            + cfg.norm_eps)).reshape(x.shape) * scale
    per_head = x / np.sqrt((x ** 2).mean(-1, keepdims=True)
                           + cfg.norm_eps) * scale
    np.testing.assert_allclose(got, whole, atol=1e-5)
    assert np.abs(got - per_head).max() > 0.5
    # "head" (PR 50: LFM2) IS that per-head form; an unknown name is refused
    head = np.asarray(qk_norm(dataclasses.replace(cfg, qk_norm="head"),
                              jnp.asarray(x), jnp.asarray(scale)))
    np.testing.assert_allclose(head, per_head, atol=1e-5)
    with pytest.raises(ValueError, match="qk_norm"):
        qk_norm(dataclasses.replace(cfg, qk_norm="group"), jnp.asarray(x),
                jnp.asarray(scale))


def test_the_benchmark_holds_the_same_reference():
    with open(REF_PATH, "rb") as a, open(os.path.join(
            ROOT, "benchmark", "reference", "olmoe_decoder.py"), "rb") as b:
        assert a.read() == b.read()


def test_preset_holds_the_published_sizes():
    from deepspeed_tpu.models import get_model_config

    m = get_model_config("olmoe-1b-7b")
    assert (m.num_layers, m.hidden_size, m.num_heads, m.kv_heads,
            m.head_dim, m.ffn_size, m.vocab_size, m.max_seq_len) == \
        (16, 2048, 16, 16, 128, 1024, 50304, 4096)
    assert (m.moe.num_experts, m.moe.top_k, m.moe.normalize_gates,
            m.moe.shared_expert_intermediate, m.moe.moe_layer_freq) == \
        (64, 8, False, None, 1)
    assert (m.qk_norm, m.norm, m.norm_eps, m.rope_theta,
            m.tie_embeddings, m.activation) == \
        ("full", "rmsnorm", 1e-5, 1e4, False, "silu_glu")
    # 6.92 B parameters, 1.28 B of them active a token
    assert abs(m.num_params() - 6.92e9) < 0.01e9


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------

ENGINE = {"block_size": 8, "num_blocks": 64, "max_seqs": 2, "chunk": 16,
          "max_seq_len": 128, "decode_window": 1}


def serve_logits(model, params, prompt, dtype, n_step=4, n_window=4):
    """Drive ``InferenceEngineV2`` by its own plans: chunked prefill, then
    ``n_step`` single decode steps, then ``n_window`` iterations in the
    decode window's form (fresh K/V staged beside the read-only pool).
    Returns {form: [(tokens so far, logits row)]}, teacher-forced on the
    engine's own argmax."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.forward import merge_step

    # (a copy: the engine donates the per-layer leaves to their stack)
    eng = InferenceEngineV2(model, params=jax.tree.map(jnp.copy, params),
                            config={**ENGINE, "dtype": dtype},
                            rng=jax.random.PRNGKey(0))
    assert "layers_stacked" in eng.params          # the scanned walk
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
        args = [(jnp.asarray(plan.slot_map),), jnp.asarray(plan.token_ids),
                jnp.asarray(plan.positions), (jnp.asarray(plan.block_tables),),
                jnp.asarray(plan.seq_lens), jnp.asarray(plan.sample_idx)]
        eng.kv_pool, logits = fwd(eng.params, eng.kv_pool, *args)
        chunks += plan.kind == "prefill"
        sampled = {}
        if plan.do_sample[0]:
            row = np.asarray(logits, np.float32)[0]
            out["prefill" if plan.kind == "prefill" else "step"].append(
                (list(seq.tokens), row))
            sampled = {1: int(np.argmax(row))}
        eng.scheduler.commit(plan, sampled)
    assert chunks >= 2                              # the prompt came in chunks
    # the window form, as ``_window_program``'s ``_iter`` calls it
    m, cfg = model.config, eng.config
    S, bs, Ws = cfg.max_seqs, cfg.block_size, 8
    tables = np.zeros((S, eng.state.max_blocks_per_seq), np.int32)
    tables[seq.slot, :len(seq.blocks)] = seq.blocks
    tables = (jnp.asarray(tables),)
    # (a tuple a kind of layer, as the window program's: one kind here)
    kbuf = vbuf = (jnp.zeros((m.num_layers, S, m.kv_heads, Ws, m.head_dim),
                             dtype),)
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
    return out


@pytest.fixture(scope="module")
def served(tiny):
    model, params, _ = tiny
    prompt = np.random.default_rng(2).integers(0, 256, 37).tolist()
    return serve_logits(model, params, prompt, jnp.float32)


@pytest.mark.parametrize("form", ["prefill", "step", "window"])
def test_serving_matches_the_reference(tiny, served, form):
    """Prefill in chunks, then decode through the paged cache, against the
    reference's FULL forward over the same tokens."""
    model, params, _ = tiny
    assert served[form]
    for toks, row in served[form]:
        want = np.asarray(reference_logits(
            model, params, np.asarray(toks, np.int32),
            rows=[len(toks) - 1]))[0]
        np.testing.assert_allclose(row, want, atol=ATOL, rtol=0,
                                   err_msg=f"{form} at {len(toks)} tokens")


def test_bf16_compute_fails_the_tolerance(tiny):
    """The tolerance is tight enough to tell precisions apart: the same
    engine computing in bfloat16 leaves it by more than an order."""
    model, params, _ = tiny
    prompt = np.random.default_rng(2).integers(0, 256, 37).tolist()
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


def test_moe_counters_follow_the_plans():
    """``moe_routed_rows`` / ``moe_padded_rows`` are host arithmetic on the
    dispatched plans' shapes."""
    from deepspeed_tpu.inference.engine_v2 import (InferenceEngineV2,
                                                   moe_padded_rows,
                                                   moe_tile_rows)
    from deepspeed_tpu.models import build_model

    model = build_model("tiny-olmoe")
    eng = InferenceEngineV2(model, rng=jax.random.PRNGKey(0),
                            config={**ENGINE, "decode_window": 4})
    eng.generate([list(range(1, 20))], max_new_tokens=9)
    st, mo = eng.stats, model.config.moe
    assert st["decode_tokens"] == 8        # 9 new: 1 by the prefill, 2 x 4
    tokens = st["prefill_tokens"] + st["decode_tokens"]
    assert st["moe_routed_rows"] == tokens * mo.top_k * 4
    assert st["moe_padded_rows"] > st["moe_routed_rows"]
    # the rule: twice the mean rows an expert, a power of two, inside
    # [floor, 128]
    assert moe_tile_rows(48, 8, 64) == 16           # a decode step: 6 rows
    assert moe_tile_rows(128, 8, 64) == 32          # one 128-token chunk
    assert moe_tile_rows(512, 8, 64) == 128
    assert moe_tile_rows(4096, 8, 64) == 128
    assert moe_tile_rows(48, 8, 64, quantised=True) == 32
    assert moe_padded_rows(48, 8, 64, 16) == 384 + 64 * 16


def test_engine_logs_one_gmm_line_a_shape():
    """The grouped GEMM's block plan is the program's own record of what
    ran: one ``gmm:`` line a distinct expert shape (gate and up share
    theirs; down is the other), however many programs — prefill steps,
    one-step decode, windows, each at its own tile height — launch it."""
    import logging

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.utils.logging import logger

    lines = []
    handler = logging.Handler()
    handler.emit = lambda rec: lines.append(rec.getMessage())
    level = logger.level            # (conftest.py quiets it to warnings)
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        model = build_model("tiny-olmoe")
        eng = InferenceEngineV2(model, rng=jax.random.PRNGKey(0),
                                config={**ENGINE, "decode_window": 4})
        eng.generate([list(range(1, 20))], max_new_tokens=9)
    finally:
        logger.setLevel(level)
        logger.removeHandler(handler)
    m = model.config
    shapes = {(p.K, p.N, p.bk, p.bn) for p in eng.gmm_plans.values()}
    assert shapes == {(m.hidden_size, m.ffn_size) * 2,
                      (m.ffn_size, m.hidden_size) * 2}      # K and N whole
    gmm = [ln for ln in lines if ln.startswith("gmm: ")]
    assert len(gmm) == 2 and len(eng._programs) >= 2
    assert all("nk 1" in ln for ln in gmm)
