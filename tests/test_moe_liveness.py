"""A row that carries no request reaches no routed expert (PR 51): the
engine's own programs — packed prefill plans with a padded chunk, decode
windows most of whose slots are empty and one of whose rows stops at its EOS
inside the window — hand the expert sort a liveness mask, and every row that
HAS a request comes out bit for bit as it does from the same programs with
every row marked live. The sort itself is held in
``tests/test_grouped_matmul.py``, the compiled form in
``tests/test_chip_compile.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import engine_v2
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model

ENGINE = {"block_size": 8, "num_blocks": 96, "max_seqs": 8, "chunk": 16,
          "max_seq_len": 128, "decode_window": 4, "dtype": jnp.float32}
#: three requests in eight slots; none of the prompts fills its last chunk
PROMPTS = {1: 21, 2: 5, 3: 35}
NEW = 9                     # 1 by the prefill, then two windows of 4


class AllLive:
    """An engine's forward with every token of every row marked live: what
    the programs computed before the sort knew of liveness."""

    def __init__(self, forward):
        self._forward = forward

    def __getattr__(self, name):
        return getattr(self._forward, name)

    def __call__(self, params, pools, token_ids, *args, **kw):
        kw["live"] = jnp.ones(token_ids.shape, bool)
        return self._forward(params, pools, token_ids, *args, **kw)


class Record:
    """Every dispatch of an engine in order: the logits its program sampled
    from (one ``[S, V]`` a forward), which of their rows carried a request,
    and what ``_count_moe`` was told and booked."""

    def __init__(self, monkeypatch):
        self.calls: list[np.ndarray] = []
        self.logits: list[tuple[np.ndarray, np.ndarray]] = []
        self.booked: list[tuple[int, int, int, int]] = []
        self.kinds: list[str] = []
        self.windows: list[list[int]] = []      # rows live, by iteration
        sample = engine_v2.sample_logits

        def tapped(logits, rng, **kw):
            jax.debug.callback(lambda a: self.calls.append(np.asarray(a)),
                               logits, ordered=True)
            return sample(logits, rng, **kw)

        monkeypatch.setattr(engine_v2, "sample_logits", tapped)

    def attach(self, eng):
        commit, count = eng._commit_entry, eng._count_moe

        def committed(entry, toks_h, emitted):
            if entry["kind"] == "window":
                W = toks_h.shape[0]
                calls, self.calls = self.calls[:W], self.calls[W:]
                self.logits += [(c, toks_h[i] >= 0)
                                for i, c in enumerate(calls)]
                self.kinds += ["window"] * W
                self.windows.append((toks_h >= 0).sum(axis=1).tolist())
            else:
                call, self.calls = self.calls[0], self.calls[1:]
                plan = entry["plan"]
                # (a prefill program's rows, then its decode block's)
                live = np.zeros(len(call), bool)
                live[[r for r, _ in plan.sampled_rows()]] = True
                self.logits.append((call, live))
                self.kinds.append(plan.kind)
            return commit(entry, toks_h, emitted)

        def counted(live_tokens, rows, iters=1):
            before = eng.stats["moe_masked_rows"]
            count(live_tokens, rows, iters=iters)
            self.booked.append((live_tokens, rows, iters,
                                eng.stats["moe_masked_rows"] - before))

        eng._commit_entry, eng._count_moe = committed, counted


def _serve(eng, eos=None):
    """The three requests through put / step / flush; {uid: generated}."""
    rng = np.random.default_rng(3)
    uids = sorted(PROMPTS)
    for uid in uids:
        eng.put(uid, rng.integers(1, 256, PROMPTS[uid]).tolist(),
                max_new_tokens=NEW, eos_token_id=(eos or {}).get(uid))
    out = {uid: [] for uid in uids}
    for _ in range(400):
        for uid, toks in eng.step().items():
            out[uid].extend(toks)
        if all(eng.query(uid)["done"] for uid in uids):
            break
    for uid in uids:
        eng.flush(uid)
    return out


#: preset -> the most rows live in one window (LFM2's plans prefill a
#: sequence at a time between windows: its requests meet two at a time, and
#: the one that stops leaves three iterations with NO row live)
PRESETS = {"tiny-olmoe": 3, "tiny-lfm2-moe": 2}


@pytest.mark.parametrize("preset", list(PRESETS))
def test_programs_mask_rows_without_a_request_and_keep_the_rest(monkeypatch,
                                                                preset):
    model = build_model(preset, dtype=jnp.float32)
    sides = {}
    for side in ("masked", "all_live"):
        rec = Record(monkeypatch)
        eng = InferenceEngineV2(model, config=dict(ENGINE),
                                rng=jax.random.PRNGKey(0))
        if side == "all_live":
            eng._forward = AllLive(eng._forward)
        rec.attach(eng)
        # a first pass finds a token at which request 2 can stop INSIDE a
        # window (not in its last iteration, and not seen before it)
        gen = _serve(eng)[2]
        at = next(i for i in range(1, NEW) if (i - 1) % 4 < 3
                  and gen[i] not in gen[:i])
        first = len(rec.logits), len(rec.windows)
        out = _serve(eng, eos={2: gen[at]})
        assert len(out[2]) == at + 1 and len(out[1]) == len(out[3]) == NEW
        sides[side] = (eng, rec, out, first, at)
    (eng, rec, out, first, at), (_, rec_b, out_b, first_b, at_b) = \
        sides["masked"], sides["all_live"]

    # the same tokens, and bit for bit the same logits wherever a row
    # carries a request; a row that carries none reads otherwise (the mask
    # reached the programs)
    assert out == out_b and (first, at) == (first_b, at_b)
    assert rec.kinds == rec_b.kinds and len(rec.logits) == len(rec_b.logits)
    dead_moved = 0
    for (a, live), (b, live_b) in zip(rec.logits, rec_b.logits):
        np.testing.assert_array_equal(live, live_b)
        np.testing.assert_array_equal(a[live], b[live])
        dead_moved += int((a[~live] != b[~live]).any())
    assert dead_moved > 0

    # what the second pass held: windows with at most 3 of 8 slots live,
    # one of which lost a row inside it (request 2 met its EOS), and a
    # packed prefill plan that pads
    windows = rec.windows[first[1]:]
    assert max(map(max, windows)) == PRESETS[preset]
    assert any(w[0] > w[-1] for w in windows)
    mo = model.config.moe
    layers = eng._moe_layers
    assert layers > 0
    padded_prefill = False
    for live_tokens, rows, iters, masked in rec.booked:
        assert masked == (rows * iters - live_tokens) * mo.top_k * layers
        padded_prefill |= iters == 1 and 1 < live_tokens < rows
    assert padded_prefill
    assert any(it == 4 and rows == 8 and 0 < lt <= 12
               for lt, rows, it, _ in rec.booked)
    st = eng.stats
    assert st["moe_masked_rows"] == sum(m for *_, m in rec.booked) > 0
    assert st["moe_routed_rows"] + st["moe_masked_rows"] == sum(
        rows * iters for _, rows, iters, _ in rec.booked) * mo.top_k * layers


def test_worker_line_names_the_masked_entries():
    """A leaving worker's line carries the expert counters where the engine
    has routed layers."""
    from deepspeed_tpu.serving.replica import EngineBackend

    backend = EngineBackend.__new__(EngineBackend)
    backend.eng = InferenceEngineV2(
        build_model("tiny-olmoe", dtype=jnp.float32), config=dict(ENGINE),
        rng=jax.random.PRNGKey(0))
    backend.eng.stats.update(replica_step_s=0.0, engine_step_s=0.0)
    assert backend.pipeline_line().endswith(" % outside the engine")
    backend.eng._count_moe(5, 8, iters=4)
    assert backend.pipeline_line().endswith(
        f"; experts: {5 * 2 * 4} entries routed, {27 * 2 * 4} masked out of "
        f"the sort, {backend.eng.stats['moe_padded_rows']} buffer rows")
