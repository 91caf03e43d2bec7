"""The serving forward's seam (``deepspeed_tpu/inference/forward.py``): ONE
shape whatever the model — a tuple a kind of layer from the engine's pools
to the forward's fresh K/V, one return form in every mode, and the pool
written by the program that called the forward, never by the forward."""
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.forward import (RaggedForward, cache_kinds,
                                             merge_step, stage_rows)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.parallel.topology import MeshConfig, MeshTopology

ENGINE = {"block_size": 8, "num_blocks": 32, "max_seqs": 2, "chunk": 16,
          "max_seq_len": 128, "decode_window": 4, "dtype": jnp.float32}
#: one kind of layer, an all-MoE stack of one kind, window + full layers
PRESETS = ["tiny-llama", "tiny-olmoe", "tiny-smallthinker"]


@pytest.fixture(scope="module")
def engines():
    built = {}

    def get(preset, layer_kinds=None, **over):
        key = (preset, layer_kinds, tuple(sorted(over.items())))
        if key not in built:
            kw = {"layer_kinds": layer_kinds} if layer_kinds else {}
            built[key] = InferenceEngineV2(
                build_model(preset, dtype=jnp.float32, **kw),
                config={**ENGINE, **over}, rng=jax.random.PRNGKey(0))
        return built[key]

    return get


def _abstract(eng, S, T, mode):
    """The forward's arguments for an ``[S, T]`` step in ``mode``, as
    shapes (``jax.eval_shape``: nothing compiles)."""
    m, cfg, kinds = eng.mcfg, eng.config, eng._kinds
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    args = [eng.params, eng.kv_pool, i32(S, T), i32(S, T),
            tuple(i32(S, k.max_blocks) for k in kinds), i32(S), i32(S)]
    kw = {}
    if mode == "window":
        buf = tuple(jax.ShapeDtypeStruct(
            (len(k.layers), S, m.kv_heads, 8, m.head_dim), cfg.dtype)
            for k in kinds)
        kw = {"kv_stage": (buf, buf), "stage_fill": i32(),
              "stage_starts": i32(S)}
    elif mode == "tree":
        kw = {"tree_mask": jax.ShapeDtypeStruct((S, T, T), jnp.uint8)}
    return args, kw


@pytest.mark.parametrize("mode", ["default", "window", "tree"])
@pytest.mark.parametrize("preset", PRESETS)
def test_forward_returns_one_structure_in_every_mode(engines, preset, mode):
    """``((k_ys, v_ys), logits)``: a ``len(kinds)``-tuple of this call's
    fresh K, the same of V (each ``[layers of the kind, S, KV, Ts, D]``),
    and the logits — for a model of one kind as for one of two, in the
    default, the window and the tree-verify mode."""
    eng = engines(preset)
    m, kinds = eng.mcfg, eng._kinds
    S, T = 2, (1 if mode == "window" else 4)
    args, kw = _abstract(eng, S, T, mode)
    out = jax.eval_shape(lambda a, kw: eng._forward(*a, **kw), args, kw)
    (k_ys, v_ys), logits = out
    assert isinstance(k_ys, tuple) and isinstance(v_ys, tuple)
    assert len(k_ys) == len(v_ys) == len(kinds) == \
        (2 if preset == "tiny-smallthinker" else 1)
    want = jax.tree.structure(((tuple(0 for _ in kinds),) * 2, 0))
    assert jax.tree.structure(out) == want
    Ts = stage_rows(T, eng.config.block_size)
    for k, ky, vy in zip(kinds, k_ys, v_ys):
        assert ky.shape == vy.shape == \
            (len(k.layers), S, m.kv_heads, Ts, m.head_dim)
    assert logits.shape == ((S, T, m.vocab_size) if mode == "tree"
                            else (S, m.vocab_size))


def _drive_to(eng, kind, T):
    """Step ``eng`` until its scheduler's next plan is a ``kind`` plan of
    ``T`` tokens a row with earlier tokens of the sequence in the pool."""
    for _ in range(32):
        plan = eng.scheduler.next_step()
        if plan.kind == kind and plan.token_ids.shape[1] == T \
                and int(plan.positions.max()) > 0:
            return plan
        eng.scheduler.mark_dispatched(plan)     # as ``_dispatch_next`` does
        fn = eng._program(plan.token_ids.shape[1], plan.token_ids.shape[0])
        eng.kv_pool, eng._last_tok, toks = fn(
            eng.params, eng.kv_pool, eng._last_tok, *eng._plan_args(plan),
            jax.random.PRNGKey(1))
        toks = np.asarray(toks)
        eng.scheduler.commit(plan, {
            uid: int(toks[r]) for r, uid in plan.sampled_rows()})
    raise AssertionError(f"no {kind} plan of {T} tokens came")


@pytest.mark.parametrize("merge,chunk,kind,T", [
    ("rows", 16, "decode", 1),       # one DUS a token
    ("pages", 16, "prefill", 16),    # whole pages (chunk % block == 0)
    ("scatter", 12, "prefill", 12),  # a page-misaligned chunk
])
def test_forward_then_merge_is_the_step_programs_pool(engines, merge, chunk,
                                                      kind, T):
    """The forward's fresh K/V merged by ``merge_step`` are, bit for bit,
    the pool the same engine's ``_program`` step writes — in each of the
    three merges a step's shape picks."""
    eng = engines("tiny-llama", chunk=chunk, decode_window=1,
                  prefill_pack=False)
    eng.put(1, list(range(1, 42)), max_new_tokens=4)
    plan = _drive_to(eng, kind, T)
    S = plan.token_ids.shape[0]
    before = jax.tree.map(np.asarray, eng.kv_pool)
    (k_ys, v_ys), logits = jax.jit(eng._forward)(
        eng.params, eng.kv_pool, plan.token_ids, plan.positions,
        (plan.block_tables,), plan.seq_lens, plan.sample_idx)
    merged = jax.jit(merge_step, static_argnums=4)(
        eng.kv_pool, (jnp.asarray(plan.slot_map),), k_ys, v_ys, T)
    # (the program donates its pool and last tokens: hand it copies)
    pool, _, toks = eng._program(T, S)(
        eng.params, jax.tree.map(jnp.copy, eng.kv_pool),
        jnp.copy(eng._last_tok), *eng._plan_args(plan),
        jax.random.PRNGKey(1))
    assert isinstance(pool, tuple) and len(pool) == len(merged) == 1
    # (less the trash block: a prefill program's decode block, none of its
    # rows live here, writes one token a row there)
    np.testing.assert_array_equal(np.asarray(pool[0])[:, :, :, 1:],
                                  np.asarray(merged[0])[:, :, :, 1:])
    assert not np.array_equal(before[0], np.asarray(merged[0]))   # it wrote
    # and the program samples from the same logits
    live = np.asarray(plan.do_sample).astype(bool)
    np.testing.assert_array_equal(
        np.asarray(toks)[:S][live], np.argmax(np.asarray(logits), -1)[live])
    eng.flush(1)


def test_one_kind_engine_holds_and_passes_one_tuples(engines):
    """A model of one kind of layer takes the same tuples as one of two:
    the engine's pool, a step program's and a window program's pool
    arguments and results are 1-tuples, and ``generate`` gives the tokens
    it gave before the forward had a module of its own."""
    eng = engines("tiny-llama", block_size=16, num_blocks=32, max_seqs=4)
    assert isinstance(eng.kv_pool, tuple) and len(eng.kv_pool) == 1
    assert eng._pool_formats == (eng._pool_format,)
    assert eng._forward.kinds == eng._kinds and len(eng._kinds) == 1
    S = eng.config.max_seqs
    z = lambda *s: np.zeros(s, np.int32)
    key = jax.random.PRNGKey(0)
    tables = (z(S, eng._kinds[0].max_blocks),)
    # (all-zero plans: every row is padding and writes the trash block)
    pool, last, _ = eng._program(1, S)(
        eng.params, eng.kv_pool, eng._last_tok, z(S, 1), z(S, 1),
        (z(S, 1),), tables, z(S), z(S), z(S), z(S), np.arange(S, dtype=np.int32),
        key)
    assert isinstance(pool, tuple) and len(pool) == 1
    pool, last, _, _ = eng._window_program(2)(
        eng.params, pool, last, z(S), np.zeros(S, np.uint8), z(S), z(S),
        tables, z(S), np.full(S, -1, np.int32), key)
    assert isinstance(pool, tuple) and len(pool) == 1
    eng.kv_pool, eng._last_tok = pool, last
    # a program of the bare form is refused, not silently rewrapped
    with pytest.raises((TypeError, ValueError)):
        eng._program(1, S)(
            eng.params, eng.kv_pool[0], eng._last_tok, z(S, 1), z(S, 1),
            z(S, 1), tables[0], z(S), z(S), z(S), z(S),
            np.arange(S, dtype=np.int32), key)
    # the tokens of commit 760be06 (float32 on the CPU, PRNGKey(0) weights)
    assert eng.generate([list(range(1, 21)), [7, 3, 9]],
                        max_new_tokens=12) == [
        [38, 38, 143, 38, 143, 143, 25, 49, 38, 49, 49, 49],
        [33, 221, 33, 221, 160, 160, 160, 160, 74, 13, 94, 13]]


@pytest.mark.parametrize("layer_kinds", [None, ("full", "full_nope")],
                         ids=["one-kind", "rope-and-nope-in-one-cache"])
def test_a_forward_builds_without_an_engine(engines, layer_kinds):
    """``RaggedForward`` is a record of what the forward reads: built here
    from a model and a config alone, over the per-layer parameter tree
    ``model.init`` makes (the unrolled walk), it gives the logits of the
    engine's own forward over the same weights stacked (the scanned walk)
    — also where ONE cache serves a period of two kinds of layer (rope and
    none: the walk knows each layer's place in the period)."""
    eng = engines("tiny-llama", layer_kinds=layer_kinds)
    assert len(eng._kinds) == 1
    model = eng.model
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    cfg = types.SimpleNamespace(**{**ENGINE, "quant_bits": None})
    fwd = RaggedForward(
        mcfg=model.config, config=cfg, kinds=cache_kinds(model.config, cfg),
        topology=MeshTopology(MeshConfig(tensor=1, data=1)), tp_ring_n=0,
        tp_ring_force=False, attn_decode_sel=eng._attn_decode_sel,
        attn_tree_sel=eng._attn_tree_sel, qkind={}, gmm_plans={})
    assert fwd.kinds == eng._kinds
    S, T = cfg.max_seqs, 8
    tok = np.arange(S * T, dtype=np.int32).reshape(S, T) % 200 + 1
    pos = np.broadcast_to(np.arange(T, dtype=np.int32), (S, T))
    args = (tok, pos, (np.zeros((S, fwd.kinds[0].max_blocks), np.int32),),
            np.full(S, T, np.int32), np.full(S, T - 1, np.int32))
    (k1, _), l1 = jax.jit(fwd)(params, eng.kv_pool, *args)
    (k2, _), l2 = jax.jit(eng._forward)(eng.params, eng.kv_pool, *args)
    assert "layers_stacked" in eng.params and "layer_0" in params
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(k1[0]), np.asarray(k2[0]),
                               atol=1e-5)


# ---- the pool write against a plain scatter (PR 55) ----------------------

_BS, _NB, _D, _L = 8, 7, 16, 2


def _slots(block, first=0, n=_BS):
    """``n`` flat slots of ``block`` from offset ``first`` on."""
    return [block * _BS + first + i for i in range(n)]


def _row(*real, T=2 * _BS):
    """A plan row's slot map: its real slots, then trash slots (block 0)."""
    real = [s for part in real for s in part]
    return real + [0] * (T - len(real))


#: name -> (a V half?, KV heads, the plan's [S, T] slot map, the decode
#: block's [S'] write slots). Trash slots are < ``_BS`` (block 0).
_MERGE_CASES = {
    # whole chunks from page boundaries, one of them a page and a half
    "kv_halves": (True, 2, [_row(_slots(2), _slots(5)),
                            _row(_slots(3), _slots(1, n=4))], [4 * _BS + 3]),
    # the latent page: one half, no V
    "latent_no_v": (False, 1, [_row(_slots(2), _slots(5)),
                               _row(_slots(3), _slots(1, n=4))],
                    [4 * _BS + 3]),
    # a ring at its wrap: the chunk's second page lands in the ring's FIRST
    # slot, and another row's window sits in the block between
    "ring_wrap": (True, 2, [_row(_slots(6), _slots(1)),
                            _row(_slots(3), _slots(4, n=2))],
                  [2 * _BS + 7, 5 * _BS]),
    # rows that may NOT page-write: one token on a page boundary, one token
    # in the middle of a page of live rows, a chunk that starts off a
    # boundary (only its first token lands: the scheduler never plans one)
    "one_token_and_off_boundary": (True, 2, [
        _row(_slots(2, n=1)), _row(_slots(4, first=5, n=1)),
        _row(_slots(5, first=3, n=4))], [3 * _BS + 1]),
    # a plan of nothing but padding beside one real row
    "empty_rows": (False, 1, [_row(), _row(_slots(3), _slots(6)), _row()],
                   [0]),
    # a decode block whose rows carry no request (trash slots), one of them
    # beside a row that shares its page with nothing live
    "block_rows_with_no_request": (True, 1, [_row(_slots(1), _slots(2))],
                                   [0, 3, 6 * _BS + 2, 0]),
}


@pytest.mark.parametrize("case", list(_MERGE_CASES))
def test_pool_write_is_a_plain_scatter_on_every_live_slot(case):
    """``merge_step`` of a plan's chunks (pages) and then of its decode
    block's one token a row (rows), as ``step_fused`` chains them, leaves
    in every slot but the trash block's what a numpy scatter of the same
    ``(slot_map, k_ys, v_ys)`` leaves — and what the pool held before
    everywhere a token did not land (less the rows of a written page past
    its chunk's real tokens: the sequence's not-yet-valid region)."""
    has_v, KV, slot_map, block_slots = _MERGE_CASES[case]
    halves = 2 if has_v else 1
    slot_map = np.asarray(slot_map, np.int32)
    block_slots = np.asarray(block_slots, np.int32)
    S, T = slot_map.shape
    rng = np.random.default_rng(len(case))
    pool0 = rng.standard_normal((_L, halves, KV, _NB, _BS, _D)).astype(
        np.float32)
    fresh = [rng.standard_normal((_L, S, KV, T, _D)).astype(np.float32)
             for _ in range(halves)]
    b_fresh = [rng.standard_normal(
        (_L, len(block_slots), KV, 1, _D)).astype(np.float32)
        for _ in range(halves)]

    def write(pool, slot_map, k, v, T):
        return merge_step((pool,), (slot_map,), (k,), (v,), T)[0]

    @jax.jit
    def step(pool, slot_map, block_slots, fresh, b_fresh):
        pool = write(pool, slot_map, fresh[0],
                     fresh[1] if has_v else None, T)
        return write(pool, block_slots[:, None], b_fresh[0],
                     b_fresh[1] if has_v else None, 1)

    got = np.asarray(step(pool0, slot_map, block_slots, fresh, b_fresh))

    want, dont_care = pool0.copy(), np.zeros((_NB, _BS), bool)
    dont_care[0] = True                                   # the trash block
    for s in range(S):
        n_real = int((slot_map[s] >= _BS).sum())
        paged = n_real > 1 and slot_map[s, 0] % _BS == 0
        for t in range(T if paged else min(T, 1)):
            slot = slot_map[s, t]
            if slot >= _BS:
                for h in range(halves):
                    want[:, h, :, slot // _BS, slot % _BS] = \
                        fresh[h][:, s, :, t]
            elif paged and slot_map[s, t - t % _BS] >= _BS:
                dont_care[slot_map[s, t - t % _BS] // _BS, t % _BS] = True
    for n, slot in enumerate(block_slots):
        if slot >= _BS:
            for h in range(halves):
                want[:, h, :, slot // _BS, slot % _BS] = b_fresh[h][:, n, :, 0]
    keep = ~dont_care
    np.testing.assert_array_equal(got[:, :, :, keep], want[:, :, :, keep])
    assert not np.array_equal(got[:, :, :, 1:], pool0[:, :, :, 1:])
