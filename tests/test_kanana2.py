"""kanana-2 (``tiny-kanana2``: latent attention on every layer — the four
MLA widths all different —, one leading dense layer, then routed experts by
sigmoid scores with a selection bias, the weights times 2.448, beside ONE
ungated shared expert; untied head) against the plain reference
``tests/reference/kanana2_decoder.py``, on seeded random weights, on the
CPU: the training model's EXPANDED form, and the serving engine's OWN
programs, which run the ABSORBED form over the latent pages — SplitFuse
prefill chunks, decode steps, decode windows and the decode rows that ride
a prefill step. Logits, never tokens (``tests/test_lfm2_moe.py:Tap``).

TOLERANCE. The float32 engine and the float32 reference differ by
summation order and by the absorb's re-association (``(q W_uk^T) c`` for
``q (W_uk c)``): ``ATOL = 2e-4`` on logits of magnitude ~1.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_lfm2_moe import ATOL, CHUNK, Tap, _prompt, serve

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REF_PATH = os.path.join(HERE, "reference", "kanana2_decoder.py")
SCALING = 2.448


def _load(path):
    spec = importlib.util.spec_from_file_location("kanana2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH)


def build(dtype=jnp.float32, **over):
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.runtime.zero.planner import unbox_params

    model = build_model("tiny-kanana2", dtype=dtype, **over)
    tokens = np.random.default_rng(0).integers(0, 256, (1, 48)).astype(
        np.int32)
    params = unbox_params(model.init(jax.random.PRNGKey(3), tokens)["params"])
    return model, params, tokens


def reference_logits(model, params, row, rows=None, **kw):
    m = model.config
    return ref.forward_logits(
        row, embed=params["embed"], unembed=params["unembed"],
        layer=lambda i: ref.program_layer(params, i),
        experts=ref.program_experts(m),
        ln_final=params["ln_final"]["scale"], theta=float(m.rope_theta),
        eps=float(m.norm_eps), top_k=m.moe.top_k,
        scaling=float(m.moe.routed_scaling_factor), rows=rows, q_block=16,
        **kw)


def hold_to_the_reference(model, params, tap, requests, out, atol=ATOL):
    """``tests/test_lfm2_moe.py``'s, over this model's reference."""
    worst = 0.0
    for uid, (prompt, max_new) in requests.items():
        gen, rows = out[uid], tap.rows[uid]
        assert len(gen) == max_new and len(rows) >= max_new
        toks = np.asarray(list(prompt) + gen, np.int32)
        want = np.asarray(reference_logits(
            model, params, toks,
            rows=np.arange(len(prompt) - 1, len(toks) - 1)))
        got = np.stack(rows[:max_new]).astype(np.float32)
        worst = max(worst, float(np.abs(got - want).max()))
        np.testing.assert_allclose(
            got, want, atol=atol, rtol=0,
            err_msg=f"uid {uid}: prompt of {len(prompt)} tokens")
    return worst


@pytest.fixture(scope="module")
def tiny():
    return build()


# ---------------------------------------------------------------------------
# the model's description, the router, the training model
# ---------------------------------------------------------------------------

def test_the_presets_are_the_published_widths_and_a_tiny_of_unlike_ones():
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models.transformer import is_moe_layer

    full = get_model_config("kanana-2-30b-a3b")
    assert (full.kv_lora_rank, full.qk_nope_head_dim, full.qk_rope_head_dim,
            full.v_head_dim, full.latent_width) == (512, 128, 64, 128, 576)
    assert [is_moe_layer(full, i) for i in range(3)] == [False, True, True]
    assert (full.moe.num_experts, full.moe.top_k,
            full.moe.routed_scaling_factor) == (128, 6, SCALING)
    assert full.moe.shared_expert_intermediate == 2 * full.ffn_size == 1536
    assert not full.moe.shared_expert_gated
    # 48 published layers: 30.3 B parameters ("30B")
    assert 29.5e9 < full.num_params() < 31.0e9
    # the cut the benchmark runs, from JSON overrides
    cut = get_model_config(
        "kanana-2-30b-a3b", num_layers=5,
        moe={"moe_layer_pattern": [False, True, True, True, True]})
    assert [is_moe_layer(cut, i) for i in range(5)] == [False] + [True] * 4
    tiny_cfg = get_model_config("tiny-kanana2")
    widths = (tiny_cfg.kv_lora_rank, tiny_cfg.qk_nope_head_dim,
              tiny_cfg.qk_rope_head_dim, tiny_cfg.v_head_dim)
    assert len(set(widths)) == 4
    # every other preset is as it was: no latent, gated shared, scale 1
    other = get_model_config("tiny-qwen2-moe")
    assert other.kv_lora_rank is None and other.latent_width == 0
    assert other.moe.shared_expert_gated
    assert other.moe.routed_scaling_factor == 1.0


def test_num_params_counts_the_latent_projections(tiny):
    model, params, _ = tiny
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert model.config.num_params() == n


def test_the_gates_sum_to_the_scaling_factor_and_the_bias_only_selects():
    """Selection follows ``s + b``, the weights are ``s`` at the chosen
    ones over their sum, times 2.448 — in the program's one routine and in
    the reference's (whose group step, with ONE group, is the identity)."""
    from deepspeed_tpu.moe.sharded_moe import topk_dropless_gating

    logits = jnp.asarray([[[2.0, 1.0, 0.5, -1.0, 0.0, -2.0]]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.9, 0.0, 0.0])
    plain = topk_dropless_gating(logits, 2, score="sigmoid_bias",
                                 bias=jnp.zeros(6), scale=SCALING)
    moved = topk_dropless_gating(logits, 2, score="sigmoid_bias", bias=bias,
                                 scale=SCALING)
    assert sorted(np.asarray(plain.experts)[0, 0]) == [0, 1]
    assert sorted(np.asarray(moved.experts)[0, 0]) == [0, 3]
    np.testing.assert_allclose(np.asarray(moved.gates).sum(-1), SCALING,
                               rtol=1e-5)
    s = 1 / (1 + np.exp(-np.asarray(logits)[0, 0]))
    chosen = np.asarray(moved.experts)[0, 0]
    np.testing.assert_allclose(np.asarray(moved.gates)[0, 0],
                               s[chosen] / s[chosen].sum() * SCALING,
                               rtol=1e-5)
    g, e = ref.route(logits[0], bias, 2, SCALING)
    assert sorted(np.asarray(e)[0]) == [0, 3]
    np.testing.assert_allclose(np.asarray(g)[0, chosen],
                               np.asarray(moved.gates)[0, 0], rtol=1e-5)
    # scale 1.0 is every other preset's: bit for bit what it was
    one = topk_dropless_gating(logits, 2, score="sigmoid_bias", bias=bias)
    assert np.array_equal(np.asarray(one.gates),
                          np.asarray(topk_dropless_gating(
                              logits, 2, score="sigmoid_bias", bias=bias,
                              scale=1.0).gates))
    # two groups of three, one kept: the reference's group step is real
    # (group 1's two best s + b, 1.17 + 0.50, beat group 0's 0.88 + 0.73:
    # the token's experts are then group 1's, not the global top two)
    _, e2 = ref.route(logits[0], bias, 2, SCALING, n_group=2, topk_group=1)
    assert sorted(np.asarray(e2)[0]) == [3, 4]


def test_the_shared_expert_is_added_ungated(tiny):
    """No ``shared_gate`` parameter; with the routed experts' output
    weights zeroed, an expert layer's feed-forward IS the shared expert."""
    model, params, tokens = tiny
    assert "shared_gate" not in params["layer_1"]["moe"]
    from deepspeed_tpu.models.transformer import DenseFFN, MoEFFN

    cfg = model.config
    p = jax.tree.map(jnp.copy, params["layer_1"]["moe"])
    p["moe_layer"]["experts"]["w_down"] = jnp.zeros_like(
        p["moe_layer"]["experts"]["w_down"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 7, 64)),
                    jnp.float32)
    got = MoEFFN(cfg).apply({"params": p}, x, mutable=["losses"])[0]
    want = DenseFFN(dataclasses.replace(
        cfg, intermediate_size=cfg.moe.shared_expert_intermediate)).apply(
        {"params": p["shared_expert"]}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_transformer_lm_expanded_form_matches_the_reference(tiny):
    model, params, tokens = tiny
    want = np.asarray(reference_logits(model, params, tokens[0]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, tokens))[0]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_the_seeded_bias_moves_some_tokens_experts(tiny):
    model, params, tokens = tiny
    with_bias, without = [], []
    reference_logits(model, params, tokens[0], routes=with_bias)
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if path[-1].key == "bias" and path[-2].key == "gate" else a, params)
    reference_logits(model, zeroed, tokens[0], routes=without)
    assert len(with_bias) == 2                       # the expert layers
    assert sum(int((np.sort(a, -1) != np.sort(b, -1)).any(-1).sum())
               for a, b in zip(with_bias, without)) > 0


def test_the_benchmark_holds_the_same_reference():
    with open(REF_PATH, "rb") as a, open(os.path.join(
            ROOT, "benchmark", "reference", "kanana2_decoder.py"),
            "rb") as b:
        assert a.read() == b.read()


# ---------------------------------------------------------------------------
# absorbed = expanded, on one layer; the kernel form = the gather form
# ---------------------------------------------------------------------------

def _latent_case(rng, S, T, lens, starts, H=4, R=24, rope=8, lanes=128,
                 bs=8, pages=6, nb=40):
    """Ragged inputs of the latent form: a pool of random rows whose lanes
    past ``R + rope`` hold GARBAGE (a form that read them would differ),
    distinct pages a slot, queries with zeros in the padding."""
    pool = rng.normal(size=(2, 1, 1, nb, bs, lanes)).astype(np.float32)
    tables = rng.permutation(np.arange(1, nb))[:S * pages].reshape(S, pages)
    q = rng.normal(size=(S, T, H, lanes)).astype(np.float32)
    q[..., R + rope:] = 0
    Ts = max(8, T)
    stage = rng.normal(size=(S, 1, Ts, lanes)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(stage),
            jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32),
            jnp.asarray(starts, jnp.int32))


def _latent_oracle(q, pool, stage, tables, lens, starts, R, scale, layer,
                   bs=8):
    """Plain numpy: each query row over its slot's rows < its position."""
    q, pool, stage = (np.asarray(a, np.float64) for a in (q, pool, stage))
    S, T, H, _ = q.shape
    out = np.zeros((S, T, H, R))
    for s in range(S):
        n, st = int(lens[s]), int(starts[s])
        rows = np.concatenate([pool[layer, 0, 0, b] for b in
                               np.asarray(tables[s])])[:st]
        rows = np.concatenate([rows, stage[s, 0, :n - st]])
        for t in range(T):
            seen = rows[:min(st + t + 1, n)]
            if not len(seen):
                continue
            sc = np.einsum("hd,cd->hc", q[s, t], seen) * scale
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[s, t] = (p / p.sum(-1, keepdims=True)) @ seen[:, :R]
    return out


#: the tiny model's latent widths (``_latent_case``'s R, rope and lanes) and
#: the per-head widths of the EXPANDED form: the forms cross at 5.8 tokens
TINY_WIDTHS = dict(R=24, dn=16, dr=8, dv=12, lanes=128)


def _expanded_case(rng, q_lat, H=4, R=24, dn=16, dr=8, dv=12, lanes=128):
    """The expanded form's operands for a latent case: queries AS
    PROJECTED and the two up-projections — and the absorbed query they
    fold to, in float64, for the oracle."""
    S, T = q_lat.shape[:2]
    q = rng.normal(size=(S, T, H, dn + dr)).astype(np.float32)
    w_uk = (rng.normal(size=(R, H, dn)) / R ** 0.5).astype(np.float32)
    w_uv = (rng.normal(size=(R, H, dv)) / R ** 0.5).astype(np.float32)
    absorbed = np.zeros((S, T, H, lanes))
    absorbed[..., :R] = np.einsum("sthd,rhd->sthr", q[..., :dn].astype(
        np.float64), w_uk.astype(np.float64))
    absorbed[..., R:R + dr] = q[..., dn:]
    return jnp.asarray(q), jnp.asarray(w_uk), jnp.asarray(w_uv), absorbed


@pytest.mark.parametrize("name, T, lens, starts", [
    # decode: contexts that end on, before and after page edges; one empty
    ("decode", 1, [17, 8, 1, 0, 40], [16, 7, 0, 0, 39]),
    # a chunk of 16 over 0, 8 and 24 cached tokens; a partial last chunk
    # (ABSORBED, as every chunk was until PR 60 and the gather path still is)
    ("chunk16", 16, [16, 24, 33, 0], [0, 8, 24, 0]),
    # a chunk of 4: under the break-even (5.8 at these widths), absorbed
    ("chunk4-under-the-break-even", 4, [4, 13, 0], [0, 9, 0]),
    # ---- the EXPANDED form (``paged_latent_prefill``) ----
    # contexts that end INSIDE a page (of 8): 3, 11 and 19 cached tokens
    ("expanded-ends-inside-a-page", 16, [19, 27, 30], [3, 11, 19]),
    # every key in the stage: first chunks, one full, one of 5 tokens
    ("expanded-all-keys-staged", 16, [16, 5], [0, 0]),
    # deep in a long context: 29 pages before the chunk, a partial chunk
    ("expanded-deep", 16, [248, 243], [232, 232]),
    # several rows, an empty slot between and one at the end
    ("expanded-empty-slots", 16, [24, 0, 33, 0], [8, 0, 24, 0]),
    # just over the break-even: 8 tokens a chunk; 32: the stage spans pages
    ("expanded-chunk8-over-the-break-even", 8, [8, 21, 47], [0, 16, 40]),
    ("expanded-chunk32", 32, [40, 70, 0], [8, 40, 0]),
])
def test_the_latent_kernel_form_matches_the_plain_oracle(name, T, lens,
                                                         starts):
    """The latent kernel's two forms in interpret mode over ragged lengths
    that cross page and tile edges. ABSORBED (``paged_ragged_attention``,
    ``value_lanes``): one row a token shared by the heads, the value the
    row's first ``R`` lanes, padding unread. EXPANDED
    (``paged_latent_prefill``): the same cache, the page up-projected a
    head in the kernel — held to the same oracle through ``W_uk`` folded
    into the query and ``W_uv`` after the weighted sum, in float64. Which
    form a chunk takes is its ``T`` against the break-even."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        latent_prefill_plan, paged_latent_prefill, paged_ragged_attention)

    rng = np.random.default_rng(7)
    R, scale = 24, 24 ** -0.5
    case = _latent_case(rng, len(lens), T, lens, starts, pages=32, nb=200)
    q, pool, stage, tables, ln, st = case
    plan = latent_prefill_plan(T, 4, block_size=8, dtype=jnp.float32,
                               **TINY_WIDTHS)
    assert (plan is not None) == (T >= 6)
    if not name.startswith("expanded"):
        # (the absorbed form serves any chunk: "chunk16" holds it to that)
        got = paged_ragged_attention(q, pool, stage, None, tables, ln, st,
                                     st, block_size=8, layer_index=1,
                                     scale=scale, value_lanes=R,
                                     interpret=True)
        assert got.shape == (len(lens), T, 4, R)
        want = _latent_oracle(q, pool, stage, tables, lens, starts, R,
                              scale, 1)
    else:
        qp, w_uk, w_uv, q_abs = _expanded_case(rng, q)
        got = paged_latent_prefill(qp, w_uk, w_uv, pool, stage, tables, ln,
                                   st, st, block_size=8, layer_index=1,
                                   scale=scale, interpret=True)
        assert got.shape == (len(lens), T, 4, 12)
        want = np.einsum("sthr,rhd->sthd", _latent_oracle(
            q_abs, pool, stage, tables, lens, starts, R, scale, 1),
            np.asarray(w_uv, np.float64))
    live = np.asarray(lens) > 0
    valid = (np.asarray(starts)[:, None] + np.arange(T)[None]
             < np.asarray(lens)[:, None])
    np.testing.assert_allclose(np.asarray(got)[valid], want[valid],
                               atol=2e-5)
    assert not np.asarray(got)[~live].any()


def test_the_expanded_form_refuses_what_it_does_not_serve():
    """A chunk under the break-even is the absorbed form's (the entry says
    so, by the numbers), and so is a pool with K/V halves."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        latent_prefill_breakeven, paged_latent_prefill)

    assert latent_prefill_breakeven(512, 128, 64, 128, 640) \
        == pytest.approx(157.54, abs=0.01)          # kanana-2's widths
    assert latent_prefill_breakeven(**TINY_WIDTHS) == pytest.approx(5.79,
                                                                    abs=0.01)
    # widths at which expanding never pays: a row narrower than a head
    assert latent_prefill_breakeven(64, 128, 64, 128, 128) == float("inf")
    rng = np.random.default_rng(2)
    q, pool, stage, tables, ln, st = _latent_case(rng, 2, 4, [4, 12],
                                                  [0, 8])
    qp, w_uk, w_uv, _ = _expanded_case(rng, q)
    kw = dict(block_size=8, layer_index=0, scale=1.0, interpret=True)
    with pytest.raises(ValueError, match="cheaper absorbed .*cross at 5.8"):
        paged_latent_prefill(qp, w_uk, w_uv, pool, stage, tables, ln, st,
                             st, **kw)
    q, pool, stage, tables, ln, st = _latent_case(rng, 2, 8, [8, 12], [0, 8])
    qp, w_uk, w_uv, _ = _expanded_case(rng, q)
    with pytest.raises(ValueError, match=r"a pool \[L, 1, 1, nb, 8, lanes"):
        paged_latent_prefill(qp, w_uk, w_uv, jnp.concatenate([pool, pool], 1),
                             stage, tables, ln, st, st, **kw)


@pytest.mark.parametrize("T, H, hg, tqb", [
    # kanana-2's chunk: 16 of its 32 heads a group, the tokens whole
    (512, 32, 16, 512),
    # a chunk four times as long: 4 heads a group, still by heads alone
    (2048, 32, 4, 2048),
    # ONE head's rows past the limit: one head a group, the tokens cut too
    (16384, 32, 1, 4096),
    (4096, 7, 1, 4096)],
    ids=["published", "chunk2048", "one-head-tokens-cut", "one-head"])
def test_the_head_group_plan_fits_the_vmem_limit(T, H, hg, tqb):
    """``latent_prefill_plan`` at kanana-2's published widths: the LARGEST
    divisor of the heads whose step — by the plan's own count of blocks,
    scratch and temporaries — stays inside ``VMEM_LIMIT_BYTES``, the
    tokens whole; a tile of tokens only where one head does not fit. (That
    Mosaic agrees with the count is ``tests/test_chip_compile.py``'s.)"""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        VMEM_LIMIT_BYTES, _latent_vmem, latent_prefill_plan)

    plan = latent_prefill_plan(T, H, 512, 128, 64, 128, 640, 128,
                               jnp.bfloat16)
    assert (plan.hg, plan.tqb, plan.n_groups) == (hg, tqb, H // hg)
    assert plan.vmem_bytes <= VMEM_LIMIT_BYTES
    assert plan.vmem_bytes == _latent_vmem(hg, tqb, 512, 128, 128, 640, 128,
                                           2)
    # the next larger group (or the whole chunk) would not have fitted
    larger = [g for g in range(hg + 1, H + 1) if H % g == 0]
    if larger:
        assert _latent_vmem(larger[0], T, 512, 128, 128, 640, 128, 2) \
            > VMEM_LIMIT_BYTES
    if tqb < T:
        assert _latent_vmem(1, 2 * tqb, 512, 128, 128, 640, 128, 2) \
            > VMEM_LIMIT_BYTES
    assert "EXPANDED" in plan.describe() and f"{hg} heads" in plan.describe()
    # a decode program's row, and the rows that ride a prefill step
    assert latent_prefill_plan(1, H, 512, 128, 64, 128, 640, 128,
                               jnp.bfloat16) is None


def test_the_latent_form_is_refused_half_said():
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_ragged_attention

    q, pool, stage, tables, ln, st = _latent_case(
        np.random.default_rng(1), 2, 1, [9, 3], [8, 2])
    with pytest.raises(ValueError, match="latent form takes a pool without"):
        paged_ragged_attention(q, pool, stage, stage, tables, ln, st, st,
                               block_size=8, layer_index=0, scale=1.0,
                               value_lanes=24, interpret=True)
    with pytest.raises(ValueError, match="the model's own scale"):
        paged_ragged_attention(q, pool, stage, None, tables, ln, st, st,
                               block_size=8, layer_index=0, value_lanes=24,
                               interpret=True)


def test_absorbed_over_the_cache_equals_expanded_on_one_layer(tiny,
                                                              pallas=True):
    """ONE layer's attention: the flax module's expanded form over a whole
    sequence against the serving forward's absorbed form — a first chunk,
    its rows merged into the latent pool, then a second chunk over them."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models.transformer import LatentAttention, Norm

    model, params, tokens = tiny
    cfg = model.config
    eng = InferenceEngineV2(
        model, params=jax.tree.map(jnp.copy, params),
        config={"block_size": 8, "num_blocks": 32, "max_seqs": 2,
                "chunk": 16, "max_seq_len": 64, "dtype": jnp.float32,
                "use_pallas_decode": pallas}, rng=jax.random.PRNGKey(0))
    out = eng.generate([tokens[0, :40].tolist()], max_new_tokens=1)
    # the pool now holds [c | k_r] of the 40 tokens, layer 0's from x =
    # embed: recompute them in the expanded module's own terms
    x = params["embed"][tokens[:, :40]]
    h = Norm(cfg).apply({"params": params["layer_0"]["ln_attn"]}, x)
    a = params["layer_0"]["attn"]
    from deepspeed_tpu.models.transformer import apply_rope, latent_row
    c, k_r = latent_row(cfg, h, a["w_dkv"], a["kv_norm"])
    pos = jnp.arange(40)[None]
    _, k_r = apply_rope(k_r[:, :, None, :], k_r[:, :, None, :], pos,
                        cfg.rope_theta)
    want_rows = np.concatenate([np.asarray(c[0]), np.asarray(k_r[0, :, 0])],
                               axis=-1)
    # (the sequence was flushed by generate: find its pages by content)
    pool = np.asarray(eng.kv_pool[0])[0, 0, 0]            # [nb, bs, lanes]
    flat = pool.reshape(-1, pool.shape[-1])
    width = cfg.latent_width
    hits = [int(np.argmin(np.abs(flat[:, :width] - r).sum(-1)))
            for r in want_rows]
    np.testing.assert_allclose(flat[hits, :width], want_rows, atol=1e-5)
    assert not flat[:, width:].any()                      # padding: zeros
    assert len(out[0]) == 1
    # the third side: the two KERNEL forms over that cache — the chunk of
    # tokens 16..31 of layer 0 over the 16 rows before it — against plain
    # attention over per-head keys and values made from the rows above
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_latent_prefill, paged_ragged_attention)
    R, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    w_uk, w_uv = a["w_uk"], a["w_uv"]
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5
    q = jnp.einsum("ste,ehd->sthd", h[:, 16:32], a["wq"])
    q_r, _ = apply_rope(q[..., dn:], q[..., dn:], pos[:, 16:32],
                        cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_r], -1)
    ops = (jnp.asarray(flat[hits[16:32]])[None, None],     # the stage
           jnp.asarray([[hits[i] // 8 for i in range(0, 40, 8)]], jnp.int32),
           jnp.asarray([32], jnp.int32), jnp.asarray([16], jnp.int32),
           jnp.asarray([16], jnp.int32))
    kw = dict(block_size=8, layer_index=0, scale=scale, interpret=True)
    expanded = paged_latent_prefill(q, w_uk, w_uv, eng.kv_pool[0], *ops, **kw)
    q_abs = jnp.pad(jnp.concatenate([jnp.einsum(
        "sthd,rhd->sthr", q[..., :dn], w_uk), q_r], -1),
        [(0, 0)] * 3 + [(0, pool.shape[-1] - width)])
    absorbed = jnp.einsum("sthr,rhd->sthd", paged_ragged_attention(
        q_abs, eng.kv_pool[0], ops[0], None, *ops[1:], value_lanes=R, **kw),
        w_uv)
    c_all, kr_all = want_rows[:32, :R], want_rows[:32, R:]
    keys = np.concatenate([
        np.einsum("cr,rhd->chd", c_all, np.asarray(w_uk)),
        np.repeat(kr_all[:, None], cfg.num_heads, 1)], -1)
    scores = np.einsum("thd,chd->htc", np.asarray(q[0]), keys) * scale
    scores[:, 16 + np.arange(16)[:, None] < np.arange(32)[None]] = -np.inf
    w = np.exp(scores - scores.max(-1, keepdims=True))
    plain = np.einsum("htc,chd->thd", w / w.sum(-1, keepdims=True),
                      np.einsum("cr,rhd->chd", c_all, np.asarray(w_uv)))
    np.testing.assert_allclose(np.asarray(expanded[0]), plain, atol=1e-5)
    np.testing.assert_allclose(np.asarray(absorbed[0]), plain, atol=1e-5)


def test_the_pool_after_a_prefill_is_the_same_whichever_form_ran(
        tiny, monkeypatch):
    """What a prefill step WRITES is the row ``[c | k_r]``, whichever form
    its chunks attended by: layer 0's rows (made from the embeddings) are
    the same BYTES after an expanded prefill and after an absorbed one;
    the layers above read the attention's output, which the two forms
    round differently, and agree to float32's last places. The expanded
    entry is what the engine's chunks call; a chunk under the break-even
    and every decode row never do."""
    import deepspeed_tpu.inference.forward as fwd
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    model, params, tokens = tiny
    calls = []
    real = fwd.paged_latent_prefill
    monkeypatch.setattr(fwd, "paged_latent_prefill", lambda q, *a, **k: (
        calls.append(q.shape[1]), real(q, *a, **k))[1])

    def pool_after(chunk):
        eng = InferenceEngineV2(
            model, params=jax.tree.map(jnp.copy, params),
            config={"block_size": 8, "num_blocks": 32, "max_seqs": 2,
                    "chunk": chunk, "max_seq_len": 64,
                    "dtype": jnp.float32}, rng=jax.random.PRNGKey(0))
        out = eng.generate([tokens[0, :40].tolist()], max_new_tokens=3)
        return np.asarray(eng.kv_pool[0]), out

    expanded, out_e = pool_after(16)
    # (traced once a program: the chunks' widths, never a decode row's 1)
    assert calls and min(calls) >= 16, calls
    n = len(calls)
    monkeypatch.setattr(fwd, "latent_prefill_plan", lambda *a, **k: None)
    absorbed, out_a = pool_after(16)
    assert len(calls) == n and out_a == out_e
    assert expanded[0].tobytes() == absorbed[0].tobytes()
    assert expanded[0].any()
    np.testing.assert_allclose(expanded[1:], absorbed[1:], atol=1e-5)
    monkeypatch.undo()
    # a chunk of 4 is under the break-even: absorbed, by its T alone
    calls.clear()
    monkeypatch.setattr(fwd, "paged_latent_prefill", lambda *a, **k: (
        calls.append(1), real(*a, **k))[1])
    pool_after(4)
    assert not calls


# ---------------------------------------------------------------------------
# serving: the engine's own programs, their logits tapped
# ---------------------------------------------------------------------------

LENGTHS = {1: 1, 2: 2, 3: CHUNK, 4: CHUNK + 1, 5: 3 * CHUNK + 5, 6: 7}


@pytest.mark.parametrize("window, pallas", [(8, True), (1, False)],
                         ids=["windows-kernel", "steps-gather"])
def test_serving_matches_the_reference(tiny, monkeypatch, window, pallas):
    """Chunked prefill, then decode through the latent pages — in decode
    windows of 8 or single decode steps, by the kernel form (interpret) or
    the gather form — against the reference's full EXPANDED forward."""
    model, params, _ = tiny
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(4)
    requests = {uid: (_prompt(rng, n), 11) for uid, n in LENGTHS.items()}
    eng, out = serve(model, params, tap, requests, decode_window=window,
                     use_pallas_decode=pallas)
    (kind,) = eng._kinds
    assert (kind.name, kind.halves, kind.heads) == ("latent", 1, 1)
    assert kind.layers == (0, 1, 2) and not kind.ring_tokens
    assert eng.state.not_a_page_chain == ""
    # the walk: the leading dense layer from its own tree, the two expert
    # layers stacked and scanned (their experts closed over whole)
    assert eng._scan_lead == 1 and "layer_0" in eng.params
    assert "layer_1" not in eng.params
    assert jax.tree.leaves(eng.params["layers_stacked"])[0].shape[0] == 2
    assert (eng.stats["windows"] > 0) == (window > 1)
    hold_to_the_reference(model, params, tap, requests, out)
    assert eng.stats["latent_rows_written"] == sum(
        len(p) + 11 - 1 for p, _ in requests.values())
    eng.state.audit()


def test_the_pool_holds_one_row_a_token_and_no_kv_pair(tiny):
    """The pool is ``[layers, 1, 1, blocks, block, lanes]``: ONE row a
    token a layer — ``kv_lora_rank + qk_rope_head_dim`` values, padded to
    whole 128-lane registers (stated: 32 of 128 at the tiny widths, 576 of
    640 at the published ones) — and no K/V halves, no per-head keys."""
    from deepspeed_tpu.inference.engine_v2 import (InferenceEngineV2,
                                                   RaggedInferenceConfig)
    from deepspeed_tpu.inference.forward import cache_kinds
    from deepspeed_tpu.models import get_model_config

    model, params, _ = tiny
    eng = InferenceEngineV2(model, params=jax.tree.map(jnp.copy, params),
                            config={"block_size": 8, "num_blocks": 16,
                                    "max_seqs": 2, "chunk": 16,
                                    "max_seq_len": 64,
                                    "dtype": jnp.float32})
    (pool,) = eng.kv_pool
    cfg = model.config
    assert pool.shape == (3, 1, 1, 16, 8, 128)
    per_token_layer = pool.size // (3 * 16 * 8)
    assert per_token_layer - (128 - cfg.latent_width) \
        == cfg.kv_lora_rank + cfg.qk_rope_head_dim == 32
    (k,) = cache_kinds(get_model_config("kanana-2-30b-a3b", num_layers=5,
                                        moe={"moe_layer_pattern":
                                             [False] + [True] * 4}),
                       RaggedInferenceConfig(block_size=128, num_blocks=4096,
                                             max_seq_len=32768))
    assert (k.row_values, k.lanes, k.halves, k.max_blocks) \
        == (576, 640, 1, 256)
    assert k.pool_shape(128) == (5, 1, 1, 4096, 128, 640)
    # per-head keys and values would be 32 x (192 + 128) values a token:
    # 16 times the row as stored, 17.8 times the row as needed
    assert 32 * (192 + 128) == 16 * k.lanes


def test_a_prefill_step_carries_riding_decode_rows(tiny, monkeypatch):
    """A long prompt's chunks run while two sequences decode: the decoding
    rows ride each prefill step as its decode block (the absorbed decode
    form INSIDE the prefill program) and windows run between the steps."""
    model, params, _ = tiny
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(5)
    requests = {1: (_prompt(rng, 9), 40), 2: (_prompt(rng, 20), 40),
                3: (_prompt(rng, 7 * CHUNK + 3), 6)}
    eng, out = serve(model, params, tap, requests, arrivals={3: 4},
                     decode_window=8, prefill_grow_chunk=False)
    assert eng.stats["fused_steps"] >= 6
    assert eng.stats["fused_decode_tokens"] >= 12
    chunks = [e for e in tap.entries if e["kind"] == "plan"
              and e["plan"].kind == "prefill" and 3 in e["plan"].uids]
    assert len(chunks) == 8 and all(
        e["plan"].token_ids.shape[1] == CHUNK for e in chunks)
    hold_to_the_reference(model, params, tap, requests, out)
    eng.state.audit()


def test_a_latent_model_whose_heads_come_out_64_wide_serves(monkeypatch):
    """kanana-2's ``hidden_size / num_heads`` is 64 over an even head
    count, for which ``kv_pack`` says two KV heads a page row — and the
    latent kind has no KV heads to pack: its attention's output goes to
    ``W_o`` as it is, whichever form made it. (The tiny preset's quotient
    is 16; at 64 the published model's prefill step did not trace.)"""
    from deepspeed_tpu.inference.forward import kv_pack

    model, params, _ = build(hidden_size=256)
    assert model.config.head_dim == 64 and kv_pack(model.config) == 2
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(11)
    requests = {1: (_prompt(rng, 5), 12), 2: (_prompt(rng, 2 * CHUNK + 3), 4)}
    eng, out = serve(model, params, tap, requests, arrivals={2: 3},
                     decode_window=4, prefill_grow_chunk=False)
    assert eng.stats["fused_steps"] >= 1
    hold_to_the_reference(model, params, tap, requests, out)
