"""LFM2-MoE (``tiny-lfm2-moe``: one leading gated-short-convolution layer
with a dense SwiGLU, then one period of attention, conv, conv, conv with
routed experts — sigmoid scores, a selection bias, q and k normalised per
head) against the plain reference ``tests/reference/lfm2_moe_decoder.py``,
on seeded random weights, on the CPU: the training model's logits, and the
serving engine's OWN programs — SplitFuse prefill chunks, decode steps and
decode windows, through the paged KV pool of the one attention layer and
the record a slot of the four conv layers. Logits, never tokens: a tap on
the programs' sampler hands out the logits each program sampled from, and
every sampled row is held to the reference's one full forward over the
tokens the sequence ended with.

TOLERANCE. The float32 engine and the float32 reference differ by
summation order only (measured 2e-6 on logits of magnitude ~1): ``ATOL =
2e-4`` leaves two orders for another BLAS. The bfloat16 engine's band
is stated and held in ``tests/test_lfm2_forms.py``.
"""
import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REF_PATH = os.path.join(HERE, "reference", "lfm2_moe_decoder.py")
ATOL = 2e-4
CHUNK = 16
ENGINE = {"block_size": 8, "num_blocks": 96, "max_seqs": 3, "chunk": CHUNK,
          "max_seq_len": 192, "dtype": jnp.float32}


def _load(path):
    spec = importlib.util.spec_from_file_location("lfm2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(REF_PATH)


def build(dtype=jnp.float32, **over):
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.runtime.zero.planner import unbox_params

    model = build_model("tiny-lfm2-moe", dtype=dtype, attn_impl="xla",
                        **over)
    tokens = np.random.default_rng(0).integers(0, 256, (1, 48)).astype(
        np.int32)
    params = unbox_params(model.init(jax.random.PRNGKey(3), tokens)["params"])
    return model, params, tokens


def reference_logits(model, params, row, rows=None, **kw):
    m = model.config
    ops, experts = ref.program_ops(m)
    return ref.forward_logits(
        row, embed=params["embed"],
        layer=lambda i: ref.program_layer(params, i), ops=ops,
        experts=experts, ln_final=params["ln_final"]["scale"],
        theta=float(m.rope_theta), eps=float(m.norm_eps),
        top_k=m.moe.top_k, rows=rows, q_block=16, **kw)


@pytest.fixture(scope="module")
def tiny():
    return build()


# ---------------------------------------------------------------------------
# the model's description, and the training model (one forward-agreement
# test: the backward is jax's own)
# ---------------------------------------------------------------------------

def test_a_stack_is_leading_layers_then_whole_periods():
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models.transformer import is_moe_layer

    tiny_cfg = get_model_config("tiny-lfm2-moe")
    assert tiny_cfg.kinds == ("conv", "full", "conv", "conv", "conv")
    full = get_model_config("lfm2-24b-a2b")
    assert [i for i, k in enumerate(full.kinds) if k == "full"] \
        == list(range(2, 40, 4))
    assert full.kinds.count("conv") == 30
    # the cut the benchmark runs: published layers 1-5, from JSON overrides
    cut = get_model_config(
        "lfm2-24b-a2b", num_layers=5, leading_kinds=["conv"],
        layer_kinds=["full", "conv", "conv", "conv"],
        moe={"moe_layer_pattern": [False, True, True, True, True]})
    assert cut.kinds == full.kinds[1:6]
    assert [is_moe_layer(cut, i) for i in range(5)] \
        == [False, True, True, True, True]
    assert [is_moe_layer(full, i) for i in range(4)] \
        == [False, False, True, True]
    assert cut.moe.num_experts == 64 and cut.moe.router_score \
        == "sigmoid_bias"
    # the period divides the layers AFTER the leading ones, or it is refused
    with pytest.raises(ValueError, match="after 1 leading"):
        dataclasses.replace(tiny_cfg, num_layers=6).kinds_period
    with pytest.raises(ValueError, match="names must be"):
        dataclasses.replace(tiny_cfg, leading_kinds=("convolution",)
                            ).kinds_period


def test_num_params_counts_each_layer_with_what_it_has(tiny):
    model, params, _ = tiny
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert model.config.num_params() == n
    from deepspeed_tpu.models import get_model_config
    # 40 published layers: 23.8 B parameters ("24B"), of which 2 dense FFs
    assert 23.0e9 < get_model_config("lfm2-24b-a2b").num_params() < 24.5e9


def test_transformer_lm_logits_match_the_reference(tiny):
    model, params, tokens = tiny
    want = np.asarray(reference_logits(model, params, tokens[0]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, tokens))[0]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_qk_norm_head_normalises_each_head_alone():
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models.transformer import qk_norm, qk_norm_shape

    cfg = get_model_config("tiny-lfm2-moe")
    assert qk_norm_shape(cfg, 4) == (cfg.head_dim,)
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 3, 4, cfg.head_dim)), jnp.float32)
    scale = jnp.linspace(0.5, 1.5, cfg.head_dim)
    got = np.asarray(qk_norm(cfg, x, scale))
    want = np.asarray(x) / np.sqrt(
        np.mean(np.square(np.asarray(x)), -1, keepdims=True)
        + cfg.norm_eps) * np.asarray(scale)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # "full" (OLMoE) normalises all heads as one vector: another function
    whole = qk_norm(dataclasses.replace(cfg, qk_norm="full"), x,
                    jnp.ones((4, cfg.head_dim)))
    assert np.abs(np.asarray(whole) - got).max() > 0.1


def test_the_bias_moves_the_selection_and_not_the_weights():
    """Logits where the bias changes the chosen experts: the weights are
    still the sigmoid scores of the chosen ones over their sum + 1e-6."""
    from deepspeed_tpu.moe.sharded_moe import topk_dropless_gating

    logits = jnp.asarray([[[2.0, 1.0, 0.5, -1.0, 0.0, -2.0]]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.9, 0.0, 0.0])
    plain = topk_dropless_gating(logits, 2, score="sigmoid_bias",
                                 bias=jnp.zeros(6))
    moved = topk_dropless_gating(logits, 2, score="sigmoid_bias", bias=bias)
    assert sorted(np.asarray(plain.experts)[0, 0]) == [0, 1]
    assert sorted(np.asarray(moved.experts)[0, 0]) == [0, 3]
    s = 1 / (1 + np.exp(-np.asarray(logits)[0, 0]))
    chosen = np.asarray(moved.experts)[0, 0]
    np.testing.assert_allclose(np.asarray(moved.gates)[0, 0],
                               s[chosen] / (s[chosen].sum() + 1e-6),
                               rtol=1e-6)
    # the reference's own router agrees, and softmax is another function
    g, e = ref.route(logits[0], bias, 2)
    assert sorted(np.asarray(e)[0]) == [0, 3]
    np.testing.assert_allclose(np.asarray(g)[0, chosen],
                               np.asarray(moved.gates)[0, 0], rtol=1e-6)
    soft = topk_dropless_gating(logits, 2)
    assert np.abs(np.asarray(soft.gates) - np.asarray(plain.gates)).max() \
        > 0.05
    with pytest.raises(ValueError, match="router score"):
        topk_dropless_gating(logits, 2, score="tanh")


def test_the_seeded_bias_moves_some_tokens_experts(tiny):
    """``gate/bias`` is seeded non-zero, so that in the model's own
    forward selection by ``s + b`` and selection by ``s`` differ somewhere:
    a router that dropped the bias would leave the reference."""
    model, params, tokens = tiny
    with_bias, without = [], []
    reference_logits(model, params, tokens[0], routes=with_bias)
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if path[-1].key == "bias" and path[-2].key == "gate" else a, params)
    reference_logits(model, zeroed, tokens[0], routes=without)
    assert len(with_bias) == 4                       # the expert layers
    moved = sum(int((np.sort(a, -1) != np.sort(b, -1)).any(-1).sum())
                for a, b in zip(with_bias, without))
    assert moved > 0


def test_capacity_gating_refuses_the_sigmoid_router():
    from deepspeed_tpu.models import get_model_config

    base = get_model_config("tiny-lfm2-moe")
    with pytest.raises(ValueError, match="dropless"):
        build(moe=dataclasses.replace(base.moe, dropless=False))


def test_the_benchmark_holds_the_same_reference():
    with open(REF_PATH, "rb") as a, open(os.path.join(
            ROOT, "benchmark", "reference", "lfm2_moe_decoder.py"),
            "rb") as b:
        assert a.read() == b.read()


# ---------------------------------------------------------------------------
# serving: the engine's own programs, their logits tapped
# ---------------------------------------------------------------------------

class Tap:
    """The logits every program of an engine samples from, in dispatch
    order, matched with the committed entries: ``rows[uid]`` is the list of
    logits rows the uid's generated tokens were sampled from."""

    def __init__(self, monkeypatch):
        from deepspeed_tpu.inference import engine_v2

        self.calls: list[np.ndarray] = []
        self.rows: dict[int, list[np.ndarray]] = {}
        self.entries: list[dict] = []
        sample = engine_v2.sample_logits

        def tapped(logits, rng, **kw):
            jax.debug.callback(lambda a: self.calls.append(np.asarray(a)),
                               logits, ordered=True)
            return sample(logits, rng, **kw)

        monkeypatch.setattr(engine_v2, "sample_logits", tapped)

    def attach(self, eng):
        commit = eng._commit_entry

        def tapped(entry, toks_h, emitted):
            self.entries.append(entry)
            if entry["kind"] == "window":
                W = toks_h.shape[0]
                calls, self.calls = self.calls[:W], self.calls[W:]
                for uid, (slot, n) in entry["sched"].items():
                    self.rows.setdefault(uid, []).extend(
                        c[slot] for c in calls[:n])
            else:
                call, self.calls = self.calls[0], self.calls[1:]
                # (a prefill program's rows, then its decode block's)
                for r, uid in entry["plan"].sampled_rows():
                    self.rows.setdefault(uid, []).append(call[r])
            return commit(entry, toks_h, emitted)

        eng._commit_entry = tapped


def serve(model, params, tap, requests, *, arrivals=None, before_put=None,
          **engine_over):
    """Run ``requests`` {uid: (prompt, max_new)} through put / step / flush
    (a request is put as soon as the engine can schedule it, in uid order —
    or at the step ``arrivals[uid]``). Returns (engine, {uid: generated})."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    eng = InferenceEngineV2(model, params=jax.tree.map(jnp.copy, params),
                            config={**ENGINE, **engine_over},
                            rng=jax.random.PRNGKey(0))
    tap.attach(eng)
    waiting = dict(sorted(requests.items()))
    out = {uid: [] for uid in requests}
    live: set[int] = set()
    for step in range(2000):
        for uid in list(waiting):
            prompt, max_new = waiting[uid]
            if (arrivals or {}).get(uid, 0) <= step \
                    and eng.can_schedule(len(prompt), max_new):
                if before_put is not None:
                    before_put(eng, uid)
                eng.put(uid, prompt, max_new_tokens=max_new)
                live.add(uid)
                del waiting[uid]
            else:
                break
        for uid, toks in eng.step().items():
            out[uid].extend(toks)
        for uid in [u for u in live if eng.query(u)["done"]]:
            eng.flush(uid)
            live.discard(uid)
        if not waiting and not live:
            break
    assert not waiting and not live
    return eng, out


def hold_to_the_reference(model, params, tap, requests, out, atol=ATOL):
    """Every logits row a program sampled from, against the reference's ONE
    full forward over the tokens the sequence ended with. Returns the
    largest difference."""
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


def _prompt(rng, n):
    return rng.integers(0, 256, n).tolist()


#: prompts that end inside a chunk, of fewer tokens than the taps (1 and 2),
#: of exactly one chunk and of one token more
LENGTHS = {1: 1, 2: 2, 3: CHUNK, 4: CHUNK + 1, 5: 3 * CHUNK + 5, 6: 7}


@pytest.mark.parametrize("window", [1, 8])
def test_serving_matches_the_reference(tiny, monkeypatch, window):
    """Chunked prefill, then decode through cache and record — in decode
    windows of 8 or single decode steps — against the reference's full
    forward. Six requests through three slots: every slot is taken again by
    a new sequence while its last occupant's record is still in memory."""
    model, params, _ = tiny
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(4)
    requests = {uid: (_prompt(rng, n), 11) for uid, n in LENGTHS.items()}
    stale = []

    def before_put(eng, uid):
        # what the slot this sequence will take holds of its last occupant
        slot = eng.state._free_slots[0]
        stale.append(float(np.abs(np.asarray(
            eng.kv_pool[1][:, slot], np.float32)).max()))

    eng, out = serve(model, params, tap, requests, decode_window=window,
                     before_put=before_put)
    assert [k.name for k in eng._kinds] == ["full", "conv"]
    assert [len(k.layers) for k in eng._kinds] == [1, 4]
    assert "layers_stacked" not in eng.params        # the unrolled walk
    assert eng.kv_pool[1].shape == (4, 3 + 1, 2, 64)
    # slots were reused with the old record still there, never zeroed
    assert len(stale) == 6 and max(stale[3:]) > 0.01
    assert (eng.stats["windows"] > 0) == (window > 1)
    kinds = {e["kind"] for e in tap.entries}
    assert kinds == ({"plan", "window"} if window > 1 else {"plan"})
    hold_to_the_reference(model, params, tap, requests, out)
    # the later chunks of the 53-token prompt started from a record (the
    # 17th token of the prompt of chunk + 1 is a decode step, not a chunk)
    assert eng.stats["conv_chunks_carried"] >= 3
    assert eng.stats["conv_chunks"] > eng.stats["conv_chunks_carried"]
    assert eng.stats["state_records_peak"] == 3
    eng.state.audit()


def test_a_long_prompt_interleaves_with_decode_windows(tiny, monkeypatch):
    """A long prompt's chunks alternate with other sequences' decode
    windows in one engine: each chunk finds the record its last chunk left
    — the windows in between span its slot and must not write it — and the
    decoding rows carry theirs through the windows."""
    model, params, _ = tiny
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(5)
    requests = {1: (_prompt(rng, 9), 40), 2: (_prompt(rng, 20), 40),
                3: (_prompt(rng, 7 * CHUNK + 3), 6)}
    eng, out = serve(model, params, tap, requests, arrivals={3: 4},
                     decode_window=8)
    order = [("window" if e["kind"] == "window" else e["plan"].kind)
             for e in tap.entries]
    long_chunks = [i for i, e in enumerate(tap.entries)
                   if e["kind"] == "plan" and 3 in e["plan"].uids
                   and e["plan"].kind == "prefill"]
    assert len(long_chunks) == 8
    between = [order[a + 1:b] for a, b in zip(long_chunks, long_chunks[1:])]
    assert sum("window" in gap for gap in between) >= 6
    assert eng.stats["conv_chunks_carried"] >= 7
    hold_to_the_reference(model, params, tap, requests, out)
    eng.state.audit()
