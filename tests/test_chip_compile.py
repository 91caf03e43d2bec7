"""Compile the main paths' Pallas kernels for a real TPU, without one.

The TPU compiler is installed in the sandbox and compiles for a chip that
is DESCRIBED, not attached (``jax.experimental.topologies``). Interpret
mode — what every other kernel test runs — never sees Mosaic's block-shape
and VMEM rules, so a kernel can pass all of them and still be refused on
the chip (the tree-verify form of the paged kernel was, for any batch of
more than one slot). These cases hand each kernel its real widths with
``interpret=False`` and assert the compiled program carries the kernel
(``tpu_custom_call``). Nothing runs: this says nothing about results.

The file name sorts early on purpose: tier-1 is cut by its clock on slow
machines, and a test the clock never reaches guards nothing.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BF16 = jnp.bfloat16
FP8 = jnp.float8_e4m3fn


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(autouse=True)
def compiled_not_cached(monkeypatch):
    """Steer the ONE interpret-mode helper to 'compiled' (under
    JAX_PLATFORMS=cpu the program still sees the CPU), and keep these
    compiles out of the persistent cache: an executable built for a
    described chip cannot be read back without one, and the failed read
    warns on every later run."""
    import deepspeed_tpu.ops.pallas as pallas_pkg
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(pallas_pkg, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---- case builders: each takes the described devices and returns
# ---- (fn, abstract args, expect_kernel) ------------------------------------

def _one(devs):
    return SingleDeviceSharding(devs[0])


def _flash(B, S, H, D):
    def build(devs):
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()
        a = _sds(_one(devs), (B, S, H, D), BF16)
        return jax.value_and_grad(loss, argnums=(0, 1, 2)), (a, a, a), True
    return build


def _ragged_args(mk, S, T, H, KV, D, bs, nb, pool_dtype, Ts=None,
                 max_pages=8, L=2):
    Ts = Ts or max(8, T)
    return (mk((S, T, H, D), BF16),
            mk((L, 2, KV, nb, bs, D), pool_dtype),
            mk((S, KV, Ts, D), BF16), mk((S, KV, Ts, D), BF16),
            mk((S, max_pages), jnp.int32), mk((S,), jnp.int32),
            mk((S,), jnp.int32), mk((S,), jnp.int32))


def _ragged(S, T, pool_dtype, tree=False, H=16, KV=16, D=64, bs=128,
            max_pages=8, window=None, ring=False):
    def build(devs):
        from deepspeed_tpu.ops.pallas.paged_attention import \
            paged_ragged_attention
        mk = lambda shape, dt: _sds(_one(devs), shape, dt)
        Ts = max(8, T)
        if Ts > bs and Ts % bs:
            Ts = -(-Ts // bs) * bs
        args = _ragged_args(mk, S, T, H, KV, D, bs, 64, pool_dtype, Ts=Ts,
                            max_pages=max_pages)
        if tree:
            args += (mk((S, T), jnp.int32), mk((S, T, T), jnp.uint8))

        def fn(q, pool, ks, vs, bt, sl, qs, ss, *t):
            return paged_ragged_attention(
                q, pool, ks, vs, bt, sl, qs, ss, block_size=bs,
                layer_index=1, window=window,
                ring_tokens=max_pages * bs if ring else None,
                tree_positions=t[0] if t else None,
                tree_mask=t[1] if t else None)
        return fn, args, True
    return build


def _latent(S, T, H=32, lanes=640, value=512, bs=128, max_pages=256):
    """The LATENT form of the ragged kernel at kanana-2's widths: ONE row
    of 576 values in 640 lanes a token, no K/V halves, 32 query heads over
    it, the value its first 512 lanes."""
    def build(devs):
        from deepspeed_tpu.ops.pallas.paged_attention import \
            paged_ragged_attention
        mk = lambda shape, dt: _sds(_one(devs), shape, dt)
        Ts = max(8, T)
        args = (mk((S, T, H, lanes), BF16),
                mk((2, 1, 1, 64, bs, lanes), BF16),
                mk((S, 1, Ts, lanes), BF16),
                mk((S, max_pages), jnp.int32), mk((S,), jnp.int32),
                mk((S,), jnp.int32), mk((S,), jnp.int32))

        def fn(q, pool, ks, bt, sl, qs, ss):
            return paged_ragged_attention(
                q, pool, ks, None, bt, sl, qs, ss, block_size=bs,
                layer_index=1, scale=192 ** -0.5, value_lanes=value)
        return fn, args, True
    return build


def _ragged_tp4(devs):
    """The ``tensor: 4`` serving layout (``inference/forward.py``):
    the kernel per shard under shard_map, heads split four ways over a
    Mesh of the described devices."""
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_ragged_attention
    mesh = Mesh(np.asarray(devs).reshape(4), ("tensor",))
    S, T, H, KV, D, bs = 8, 1, 16, 16, 64, 128
    specs = (P(None, None, "tensor", None),
             P(None, None, "tensor", None, None, None),
             P(None, "tensor", None, None), P(None, "tensor", None, None),
             P(None, None), P(None), P(None), P(None))

    def mk_for(spec):
        return lambda shape, dt: _sds(NamedSharding(mesh, spec), shape, dt)

    shapes = _ragged_args(lambda shape, dt: (shape, dt), S, T, H, KV, D,
                          bs, 64, BF16)
    args = tuple(mk_for(sp)(*sd) for sp, sd in zip(specs, shapes))

    def kernel(q, pool, ks, vs, bt, sl, qs, ss):
        return paged_ragged_attention(q, pool, ks, vs, bt, sl, qs, ss,
                                      block_size=bs, layer_index=1)

    fn = jax.shard_map(kernel, mesh=mesh, in_specs=specs,
                       out_specs=P(None, None, "tensor", None),
                       check_vma=False)
    return fn, args, True


def _flash_train_mesh(devs):
    """The attention of the benchmark's train step (``mistral7b-zero3-sft``:
    micro-batch 2 a chip, sequence 2048, 32 heads over 8, head 128, bf16,
    forward and backward) as the dispatcher places it on mesh ``{fsdp: 4}``
    of the described chips: the flash kernel per shard inside a
    ``shard_map``, specs from the model's own logical names under the
    engine's rules. A Mosaic refusal shows here, before a four-chip call."""
    import flax.linen as nn

    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.models.transformer import (attention_sharding,
                                                  default_activation_rules)
    from deepspeed_tpu.ops.attention import (attention_formulation,
                                             dot_product_attention)
    from deepspeed_tpu.parallel.axes import model_mesh_scope
    from deepspeed_tpu.parallel.topology import MeshTopology

    topo = MeshTopology({"fsdp": 4}, devices=list(devs))
    m = get_model_config("mistral-7b", sliding_window=None)
    with nn.logical_axis_rules(default_activation_rules(topo)), \
            model_mesh_scope(topo.mesh):
        sharding = attention_sharding(m)
    B, S = 2 * 4, 2048
    q = _sds(NamedSharding(topo.mesh, sharding.q_spec),
             (B, S, m.num_heads, m.head_dim), BF16)
    kv = _sds(NamedSharding(topo.mesh, sharding.kv_spec),
              (B, S, m.kv_heads, m.head_dim), BF16)
    assert attention_formulation(q, kv, kv, sharding=sharding) \
        == ("pallas", "")

    def loss(q, k, v):
        return dot_product_attention(
            q, k, v, causal=True, sharding=sharding).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2)), (q, kv, kv), True


def _flash_inside_manual_dp(devs):
    """The same attention where the step's own ``shard_map`` has already
    made the DP axis manual (ZeRO++, 1-bit Adam: ``axis_names={fsdp}``, the
    other five axes of the engine's mesh automatic, all of size one).
    Mosaic lowers a kernel only where EVERY mesh axis is manual, so the
    dispatcher maps the rest itself; interpret mode never sees that rule."""
    from deepspeed_tpu.models import get_model_config
    from deepspeed_tpu.ops.attention import (AttentionSharding,
                                             dot_product_attention)
    from deepspeed_tpu.parallel.topology import MeshTopology

    mesh = MeshTopology({"fsdp": 4}, devices=list(devs)).mesh
    m = get_model_config("mistral-7b", sliding_window=None)
    # the rules the engine keeps inside that region (the BATCH rule names
    # the manual axis and is dropped): heads over ('tensor', 'seq')
    heads = P(None, None, ("tensor", "seq"), None)
    sharding = AttentionSharding(mesh, heads, P(None, None, None, None))
    rows = P("fsdp", None, None, None)

    def loss(q, k, v):
        def shard(q, k, v):
            return dot_product_attention(q, k, v, causal=True,
                                         sharding=sharding)
        out = jax.shard_map(shard, mesh=mesh, axis_names={"fsdp"},
                            in_specs=(rows, rows, rows), out_specs=rows,
                            check_vma=False)(q, k, v)
        return out.astype(jnp.float32).sum()

    q = _sds(NamedSharding(mesh, rows), (8, 2048, m.num_heads, m.head_dim),
             BF16)
    kv = _sds(NamedSharding(mesh, rows), (8, 2048, m.kv_heads, m.head_dim),
              BF16)
    return jax.value_and_grad(loss, argnums=(0, 1, 2)), (q, kv, kv), True


def _grouped(backward):
    def build(devs):
        from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul
        one = _one(devs)
        n, E, F, Tp, bm = 64, 2048, 1024, 64 * 128, 128
        x = _sds(one, (Tp, E), BF16)
        w = _sds(one, (n, E, F), BF16)
        te = _sds(one, (Tp // bm,), jnp.int32)
        if not backward:
            return (lambda x, w, te: grouped_matmul(x, w, te, bm),
                    (x, w, te), True)

        def loss(x, w, te):
            return grouped_matmul(x, w, te, bm).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1)), (x, w, te), True
    return build


def _grouped_serving(tokens, down=False, k=8, E=2048, F=1024, n=64):
    """The serving form at OLMoE-1B-7B's widths (64 experts, 8 a token,
    hidden 2048, expert width 1024): the depth-stacked slab with the layer
    picked inside the kernel, the sort's ``n_tiles`` skipping the buffer's
    tail, at the tile height ``moe_tile_rows`` gives a step of ``tokens``
    rows (48: a decode step, 16 rows a tile; 2048: a prefill step, 128)."""
    def build(devs):
        from deepspeed_tpu.inference.engine_v2 import (moe_padded_rows,
                                                       moe_tile_rows)
        from deepspeed_tpu.ops.pallas.grouped_matmul import \
            grouped_matmul_layer
        one = _one(devs)
        L = 2
        bm = moe_tile_rows(tokens, k, n)
        Tp = moe_padded_rows(tokens, k, n, bm)
        K, N = (F, E) if down else (E, F)

        def fn(x, w, te, nt, li):
            return grouped_matmul_layer(x, w, te, nt, bm, layer_index=li)
        return fn, (_sds(one, (Tp, K), BF16), _sds(one, (L, n, K, N), BF16),
                    _sds(one, (Tp // bm,), jnp.int32),
                    _sds(one, (), jnp.int32), _sds(one, (), jnp.int32)), True
    return build


def _routed_layer(tokens, k=8, E=2048, F=1024, masked=False):
    """One routed-expert layer of serving as ``moe/layer.py`` builds it
    (sort, fill, three grouped GEMMs, combine) at OLMoE's or SmallThinker's
    widths and the tile height of a step of ``tokens`` rows; ``masked``:
    with the rows' liveness, as every serving program hands it (PR 51)."""
    def build(devs):
        from deepspeed_tpu.inference.engine_v2 import moe_tile_rows
        from deepspeed_tpu.moe.layer import dropless_dispatch_combine
        from deepspeed_tpu.ops.pallas.grouped_matmul import \
            grouped_matmul_layer
        one = _one(devs)
        n = 64
        bm = moe_tile_rows(tokens, k, n)

        def fn(x, gates, experts, wg, wu, wd, live=None):
            def gemm(buf, srt):
                mm = lambda a, w: grouped_matmul_layer(
                    a, w, srt.tile_expert, srt.n_tiles, bm)
                return mm(jax.nn.silu(mm(buf, wg)) * mm(buf, wu), wd)
            return dropless_dispatch_combine(x, gates, experts, n, k, bm,
                                             gemm, live=live)
        return fn, (_sds(one, (tokens, E), BF16),
                    _sds(one, (tokens, k), jnp.float32),
                    _sds(one, (tokens, k), jnp.int32),
                    _sds(one, (n, E, F), BF16), _sds(one, (n, E, F), BF16),
                    _sds(one, (n, F, E), BF16)) + (
                        (_sds(one, (tokens,), jnp.bool_),) * masked), True
    return build


def _quant(bits, M, N=1024):
    def build(devs):
        from deepspeed_tpu.ops.pallas.quant_matmul import (SMALL_M_XLA,
                                                           quant_matmul,
                                                           quantize_weight)
        one = _one(devs)
        K = 1024
        qw = jax.eval_shape(lambda w: quantize_weight(w, bits),
                            jax.ShapeDtypeStruct((K, N), BF16))
        qw = jax.tree.map(lambda a: _sds(one, a.shape, a.dtype), qw)
        # int8/fp8 at decode-sized M go to XLA's fused dequant-dot by
        # design (quant_matmul.SMALL_M_XLA); int4 always runs the kernel
        expect = not (bits in (8, "fp8") and M <= SMALL_M_XLA)
        return quant_matmul, (_sds(one, (M, K), BF16), qw), expect
    return build


MISTRAL = dict(H=32, KV=8, D=128, max_pages=128)
#: SmallThinker-21B-A3B: 28 query heads over 4 (groups of SEVEN), head 128;
#: a global layer's table of 16384 / 128 pages and a window layer's ring of
#: ceil((4096 + 512) / 128) + 1 slots; 64 experts of 768, 6 a token
THINKER = dict(H=28, KV=4, D=128)
THINKER_RING = dict(max_pages=37, window=4096, ring=True, **THINKER)
THINKER_MOE = dict(k=6, E=2560, F=768)
def _latent_expanded(S, T, H=32, R=512, dn=128, dr=64, dv=128, lanes=640,
                     bs=128, max_pages=256):
    """The EXPANDED form of the latent kind's prefill chunk at kanana-2's
    widths (``paged_latent_prefill``, PR 60): the query as projected, the
    two up-projections whole, the same pool, stage and table."""
    def build(devs):
        from deepspeed_tpu.ops.pallas.paged_attention import \
            paged_latent_prefill
        mk = lambda shape, dt: _sds(_one(devs), shape, dt)
        Ts = -(-max(8, T) // bs) * bs if T > bs else max(8, T)
        args = (mk((S, T, H, dn + dr), BF16), mk((R, H, dn), BF16),
                mk((R, H, dv), BF16), mk((2, 1, 1, 64, bs, lanes), BF16),
                mk((S, 1, Ts, lanes), BF16),
                mk((S, max_pages), jnp.int32), mk((S,), jnp.int32),
                mk((S,), jnp.int32), mk((S,), jnp.int32))

        def fn(q, w_uk, w_uv, pool, ks, bt, sl, qs, ss):
            return paged_latent_prefill(
                q, w_uk, w_uv, pool, ks, bt, sl, qs, ss, block_size=bs,
                layer_index=1, scale=(dn + dr) ** -0.5)
        return fn, args, True
    return build


#: kanana-2-30b-a3b: 128 experts of 768 over hidden 2048, 6 a token
KANANA_MOE = dict(k=6, E=2048, F=768, n=128)
OLMOE = dict(H=16, KV=16, D=128, max_pages=32)

CASES = {
    "flash_fwd_bwd_b8_s1024_h16_d64": _flash(8, 1024, 16, 64),
    "flash_fwd_bwd_b1_s8192_h16_d64": _flash(1, 8192, 16, 64),
    "flash_fwd_bwd_b1_s16384_h16_d64": _flash(1, 16384, 16, 64),
    "ragged_decode_bf16": _ragged(8, 1, BF16),
    "ragged_decode_fp8": _ragged(8, 1, FP8),
    "ragged_chunk512_bf16": _ragged(1, 512, BF16),
    "ragged_chunk512_fp8": _ragged(1, 512, FP8),
    "ragged_tree_s8_bf16": _ragged(8, 8, BF16, tree=True),
    "ragged_tree_s8_fp8": _ragged(8, 8, FP8, tree=True),
    # 24 nodes at page 16: the stage (and the ancestors mask) spans 2 pages
    "ragged_tree_s8_t24_page16": _ragged(8, 24, BF16, tree=True, bs=16),
    # kanana-2's latent page: 48 decode rows, and a 512-token chunk (16,384
    # query rows: 16 tiles of 1,024) over a table of 256 pages
    "latent_decode_s48_p256": _latent(48, 1),
    "latent_chunk512_s1_p256": _latent(1, 512),
    "latent_chunk512_s12_p256": _latent(12, 512),
    # ... and the chunk's EXPANDED form (what the engine's prefill steps
    # launch since PR 60): 16 heads a group by the plan; a chunk of 2,048
    # (4 heads a group) and one of 1,536 (8: the plan's count within 3 % of
    # the limit); ONE head a group with the tokens tiled too
    "latent_expanded_chunk512_s1_p256": _latent_expanded(1, 512),
    "latent_expanded_chunk512_s12_p256": _latent_expanded(12, 512),
    "latent_expanded_chunk2048_s1_p256": _latent_expanded(1, 2048),
    "latent_expanded_chunk1536_s2_p256": _latent_expanded(2, 1536),
    "latent_expanded_chunk8192_h2_s1_p256": _latent_expanded(1, 8192, H=2),
    "grouped_gemm_fwd": _grouped(False),
    "grouped_gemm_bwd": _grouped(True),
    "grouped_gemm_olmoe_decode_up": _grouped_serving(48),
    "grouped_gemm_olmoe_decode_down": _grouped_serving(48, down=True),
    "grouped_gemm_olmoe_prefill_up": _grouped_serving(2048),
    "grouped_gemm_olmoe_prefill_down": _grouped_serving(2048, down=True),
    # OLMoE's attention geometry: one query head a KV head, head 128
    "ragged_decode_h16_kv16_d128": _ragged(48, 1, BF16, D=128),
    "ragged_chunk128_h16_kv16_d128": _ragged(4, 128, BF16, D=128),
    "ragged_chunk1536_h16_kv16_d128": _ragged(1, 1536, BF16, D=128),
    # the two geometries the benchmark serves, at their table widths: 48
    # slots x 128 columns of Mistral's 32 heads over 8, 48 x 32 of OLMoE's
    # 16 over 16 — the work list is sized by that rectangle
    "ragged_decode_mistral_s48_p128": _ragged(48, 1, BF16, **MISTRAL),
    "ragged_chunk128_mistral_s8_p128": _ragged(8, 128, BF16, **MISTRAL),
    "ragged_tree_mistral_s48_p128": _ragged(48, 8, BF16, tree=True,
                                            **MISTRAL),
    "ragged_decode_olmoe_s48_p32": _ragged(48, 1, BF16, **OLMOE),
    "ragged_chunk128_olmoe_s8_p32": _ragged(8, 128, BF16, **OLMOE),
    "ragged_tree_olmoe_s48_p32": _ragged(48, 8, BF16, tree=True, **OLMOE),
    "ragged_decode_thinker_global_s48_p128": _ragged(
        48, 1, BF16, max_pages=128, **THINKER),
    "ragged_decode_thinker_ring_s48_p37": _ragged(48, 1, BF16,
                                                  **THINKER_RING),
    "ragged_chunk512_thinker_global_s6_p128": _ragged(
        6, 512, BF16, max_pages=128, **THINKER),
    "ragged_chunk512_thinker_ring_s6_p37": _ragged(6, 512, BF16,
                                                   **THINKER_RING),
    # the cell's own prefill programs: one or two rows of a 512-token chunk,
    # or one row of two (a packed plan), 3,584 / 7,168 query rows a KV head
    # in tiles of 896 / 1,024 (``paged_plan``) — a VMEM refusal shows here
    **{f"ragged_chunk{T}_thinker_{kind}_s{S}_p{geo['max_pages']}":
       _ragged(S, T, BF16, **geo)
       for T in (512, 1024) for S in (1, 2) for kind, geo in (
           ("global", dict(max_pages=128, **THINKER)),
           ("ring", THINKER_RING))},
    "grouped_gemm_thinker_decode_up": _grouped_serving(48, **THINKER_MOE),
    "grouped_gemm_thinker_decode_down": _grouped_serving(
        48, down=True, **THINKER_MOE),
    "grouped_gemm_thinker_prefill_up": _grouped_serving(512, **THINKER_MOE),
    "grouped_gemm_thinker_prefill_down": _grouped_serving(
        512, down=True, **THINKER_MOE),
    # a decode step of 48 rows (16 rows a tile) and a prefill step of 12
    # rows x 512 tokens with its 48 riding rows (128)
    "grouped_gemm_kanana_decode_up": _grouped_serving(48, **KANANA_MOE),
    "grouped_gemm_kanana_decode_down": _grouped_serving(
        48, down=True, **KANANA_MOE),
    "grouped_gemm_kanana_prefill_up": _grouped_serving(6192, **KANANA_MOE),
    "grouped_gemm_kanana_prefill_down": _grouped_serving(
        6192, down=True, **KANANA_MOE),
    "quant_int8_m8": _quant(8, 8),
    "quant_int8_m512": _quant(8, 512),
    "quant_int4_m8": _quant(4, 8),
    "quant_int4_m512": _quant(4, 512),
    "quant_fp8_m8": _quant("fp8", 8),
    "quant_fp8_m512": _quant("fp8", 512),
    "quant_int8_m512_vocab50257": _quant(8, 512, N=50257),
    "ragged_decode_shard_map_tp4": _ragged_tp4,
    "flash_fwd_bwd_train_mesh_fsdp4": _flash_train_mesh,
    "flash_fwd_bwd_inside_manual_fsdp4": _flash_inside_manual_dp,
}


#: a routed layer whole: (tokens of the step, widths) -> the fill's form
ROUTED_LAYERS = {
    "routed_layer_thinker_decode": ((48, THINKER_MOE), "dense"),
    "routed_layer_thinker_chunk512": ((512, THINKER_MOE), "dense"),
    "routed_layer_olmoe_decode": ((48, {}), "dense"),
    "routed_layer_olmoe_chunk128": ((128, {}), "dense"),
    "routed_layer_olmoe_rows2048": ((2048, {}), "gather"),
    # the same with the liveness mask: the decode window's 48 rows at 6 of
    # 64 and tile 16, a prefill chunk, and the gather form
    "routed_layer_thinker_decode_masked": (
        (48, {**THINKER_MOE, "masked": True}), "dense"),
    "routed_layer_thinker_chunk512_masked": (
        (512, {**THINKER_MOE, "masked": True}), "dense"),
    "routed_layer_olmoe_rows2048_masked": ((2048, {"masked": True}),
                                           "gather"),
    # a (512, 1) prefill program's stream since PR 52: the chunk's tokens
    # and the decode block's 48 rows together, past the one-hot fill
    "routed_layer_thinker_chunk512_block48_masked": (
        (560, {**THINKER_MOE, "masked": True}), "gather"),
}


@pytest.mark.parametrize("name", list(ROUTED_LAYERS))
def test_routed_layer_compiles_with_no_scatter(name, topo):
    """The compiled layer holds no ``scatter`` instruction (the parent's
    held three: the fill, the destinations, bincount's add; a TPU walks
    each one update at a time), its fill is the form the step's shape
    picks — a convolution under ``moe_dispatch`` for few tokens, a gather
    for many — and the three grouped GEMMs are still the kernel; with the
    liveness mask as without."""
    import re

    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    (tokens, widths), form = ROUTED_LAYERS[name]
    fn, args, _ = _routed_layer(tokens, **widths)(topo.devices)
    assert (tokens * args[0].shape[1] <= gm.DENSE_FILL_MAX_ELEMS) \
        == (form == "dense")
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert not re.findall(r"= \S+ scatter\(", text)
    assert _kernel_calls(text) == ["grouped_matmul_fwd"] * 3
    dispatch = [ln for ln in text.splitlines() if "/moe_dispatch/" in ln]
    assert any(" convolution(" in ln for ln in dispatch) == (form == "dense")
    assert any(re.search(r" gather\(.*slice_sizes=\{1,\d{4}\}", ln)
               for ln in dispatch) == (form == "gather")


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, topo):
    fn, args, expect_kernel = CASES[name](topo.devices)
    compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == expect_kernel


@pytest.mark.parametrize("name", [n for n in CASES if n.startswith(
    ("grouped_gemm_olmoe_", "grouped_gemm_thinker_",
     "grouped_gemm_kanana_"))])
def test_grouped_gemm_block_fits_the_plans_budget(name, topo, monkeypatch):
    """The decode form (16 rows a tile) and the 128-row prefill form at the
    published OLMoE and SmallThinker widths take their expert matrix in ONE
    block (``gmm_plan``: K and N whole), and what Mosaic needs for it is
    inside the plan's own estimate: the launch compiles with the scoped
    VMEM limit pulled down from ``VMEM_LIMIT_BYTES`` to the budget."""
    import deepspeed_tpu.ops.pallas.grouped_matmul as gm

    monkeypatch.setattr(gm, "VMEM_LIMIT_BYTES", gm.VMEM_BUDGET_BYTES)
    fn, args, _ = CASES[name](topo.devices)
    x, w = args[0], args[1]
    plan = gm.gmm_plan(w.shape[-2], w.shape[-1],
                       x.shape[0] // args[2].shape[0], x.dtype)
    assert (plan.bk, plan.bn, plan.steps_per_tile) == (*w.shape[-2:], 1)
    assert plan.vmem_bytes <= gm.VMEM_BUDGET_BYTES
    assert _kernel_calls(jax.jit(fn).lower(*args).compile().as_text()) \
        == ["grouped_matmul_fwd"]


@pytest.mark.parametrize("name, kernel", [
    ("ragged_decode_bf16", "paged_attn_decode"),
    ("ragged_chunk512_bf16", "paged_attn_prefill"),
    ("ragged_tree_s8_bf16", "paged_attn_tree"),
    ("latent_decode_s48_p256", "paged_latent_decode"),
    ("latent_chunk512_s1_p256", "paged_latent_prefill"),
    # the expanded form keeps the name the ledger's breakdown compares by
    ("latent_expanded_chunk512_s1_p256", "paged_latent_prefill"),
    ("ragged_decode_h16_kv16_d128", "paged_attn_decode"),
    ("ragged_chunk128_h16_kv16_d128", "paged_attn_prefill"),
    ("ragged_decode_mistral_s48_p128", "paged_attn_decode"),
    ("ragged_chunk128_mistral_s8_p128", "paged_attn_prefill"),
    ("ragged_tree_mistral_s48_p128", "paged_attn_tree"),
    ("ragged_decode_olmoe_s48_p32", "paged_attn_decode"),
    ("ragged_chunk128_olmoe_s8_p32", "paged_attn_prefill"),
    ("ragged_tree_olmoe_s48_p32", "paged_attn_tree"),
    # a planned query tile (896 / 1,024 rows, PR 47) is the same form
    ("ragged_chunk512_thinker_global_s1_p128", "paged_attn_prefill"),
    ("ragged_chunk512_thinker_ring_s2_p37", "paged_attn_prefill"),
    ("ragged_chunk1024_thinker_global_s2_p128", "paged_attn_prefill"),
    ("ragged_chunk1024_thinker_ring_s1_p37", "paged_attn_prefill"),
    ("grouped_gemm_olmoe_decode_up", "grouped_matmul_fwd"),
    ("grouped_gemm_olmoe_prefill_down", "grouped_matmul_fwd"),
    # the whole K and V of a (row, kv head) at sequence 2048, head 128 stay
    # resident: ONE merged backward kernel (what ``train_flash_attn_mfu``
    # matches in the benchmark's train cell since PR 35)
    ("flash_fwd_bwd_train_mesh_fsdp4",
     {"flash_attention_fwd", "flash_attention_bwd_dqkv"}),
])
def test_paged_kernel_instruction_is_named_by_form(name, kernel, topo):
    """``name=`` on the ``pallas_call`` is what the compiled custom call's
    HLO instruction is called — and so what a device trace's "XLA Ops" line
    prints (unnamed, it took the innermost scope or ``closed_call``). The
    benchmark's roofline readers match it by form."""
    fn, args, _ = CASES[name](topo.devices)
    calls = _kernel_calls(jax.jit(fn).lower(*args).compile().as_text())
    assert calls and set(calls) \
        == (kernel if isinstance(kernel, set) else {kernel})


@pytest.mark.parametrize("name, shape, backward, kernels", [
    # gpt2-350m's rows, the shape the old block policy was measured on
    ("flash_fwd_bwd_b8_s1024_h16_d64", (8, 1024, 16, 64), "merged",
     {"fwd", "bwd_dqkv"}),
    # sequence 8192 at head 64: K, V (2 x 1 MiB, 2 x 2 MiB lane-padded), the
    # dk/dv blocks and their fp32 scratch still fit the plan's budget and
    # stay resident: merged; at 16384 they do not: keys come a block a grid
    # step and the backward is the split pair
    ("flash_fwd_bwd_b1_s8192_h16_d64", (1, 8192, 16, 64), "merged",
     {"fwd", "bwd_dqkv"}),
    ("flash_fwd_bwd_b1_s16384_h16_d64", (1, 16384, 16, 64), "split",
     {"fwd", "bwd_dq", "bwd_dkv"}),
])
def test_flash_backward_form_is_the_plans(name, shape, backward, kernels,
                                          topo):
    """``flash_plan`` says which backward a shape takes, and the compiled
    program launches exactly those kernels (outside a ``shard_map`` the
    instruction carries the transform's prefix: ``jvp_…``)."""
    import re

    from deepspeed_tpu.ops.pallas.flash_attention import flash_plan

    plan = flash_plan(shape, shape, BF16, True)
    assert plan.backward == backward
    assert plan.resident == (backward == "merged")
    fn, args, _ = CASES[name](topo.devices)
    calls = _kernel_calls(jax.jit(fn).lower(*args).compile().as_text())
    assert {re.search(r"flash_attention_(fwd|bwd_[a-z]+)", c).group(1)
            for c in calls} == kernels


def _kernel_calls(hlo_text):
    """The Pallas custom calls of a compiled program by the kernel's
    ``name=`` (the instruction's name less its numeric suffix)."""
    import re

    return [re.sub(r"[.\d]+$", "", c) for c in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo_text)]


def _mistral_cell(devs, layers, remat_policy):
    """``layers`` Mistral-7B blocks as the benchmark's train cell runs them
    (2 rows x 2048 a chip, bf16, every block rematted) on mesh
    ``{fsdp: 4}`` of the described chips. Returns the topology, the abstract
    parameters still in their flax boxes, the abstract ``input_ids`` and
    ``grad_step(params, ids) -> (loss, grads)``."""
    import flax.linen as nn

    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.models.loss import lm_loss_fn
    from deepspeed_tpu.models.transformer import default_activation_rules
    from deepspeed_tpu.parallel.axes import model_mesh_scope
    from deepspeed_tpu.parallel.topology import BATCH_AXES, MeshTopology

    topo = MeshTopology({"fsdp": 4}, devices=list(devs))
    model = build_model("mistral-7b", sliding_window=None, vocab_size=32768,
                        num_layers=layers, max_seq_len=2048, remat=True,
                        remat_policy=remat_policy)
    ids = _sds(NamedSharding(topo.mesh, P(BATCH_AXES)), (8, 2048), jnp.int32)
    boxed = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]

    def grad_step(p, ids):
        with nn.logical_axis_rules(default_activation_rules(topo)), \
                model_mesh_scope(topo.mesh):
            return jax.value_and_grad(
                lambda p: lm_loss_fn(model, p, {"input_ids": ids}))(p)
    return topo, boxed, ids, grad_step


def _mistral_grad_step(devs, layers, remat_policy):
    """Loss and gradients of the cell's blocks, compiled: parameters
    abstract and sharded over ``fsdp`` on their first dimension."""
    topo, boxed, ids, grad_step = _mistral_cell(devs, layers, remat_policy)
    params = jax.tree.map(
        lambda b: _sds(NamedSharding(topo.mesh, P("fsdp")), b.value.shape,
                       BF16),
        boxed, is_leaf=lambda l: hasattr(l, "names"))
    return jax.jit(grad_step).lower(params, ids).compile()


def test_first_remat_rung_keeps_the_kernel_count_and_drops_matmuls(topo):
    """What ``remat_policy="auto"`` keeps first (``save_matmul_products``)
    against ``nothing_saveable``, compiled: still two ``flash_attention_fwd``
    calls a layer — the kernel's ``(out, lse)`` carry no tag, and the
    benchmark's ``train_flash_attn_mfu`` counts two — and fewer matmuls,
    for more temporaries."""
    import re

    layers, got = 2, {}
    for policy in ("save_matmul_products", "nothing_saveable"):
        compiled = _mistral_grad_step(topo.devices, layers, policy)
        text = compiled.as_text()
        got[policy] = (_kernel_calls(text).count("flash_attention_fwd"),
                       len(re.findall(r"= \S+ convolution\(", text)),
                       compiled.memory_analysis().temp_size_in_bytes)
        print(policy, "flash forwards, matmuls, temporaries:", got[policy])
    top, bottom = got["save_matmul_products"], got["nothing_saveable"]
    assert top[0] == bottom[0] == 2 * layers
    # q, k, v, the output projection, gate and up: not made again
    assert bottom[1] - top[1] >= 2 * layers
    assert top[2] > bottom[2]


def _engine_tail(topo, boxed):
    """What ``DeepSpeedEngine._apply_grads`` reads of an engine, for the
    benchmark's train cell: its config (bf16, AdamW, ZeRO-3: the sentinel
    guards the step), optimizer, constant lr and the plan of these
    parameters — so that the engine's OWN method is what compiles (an
    engine places real arrays; a described chip holds none). Returns the
    stand-in and the abstract ``TrainState``."""
    import json
    import types
    from pathlib import Path

    from deepspeed_tpu.config import Config
    from deepspeed_tpu.ops.optimizers import OptState, build_optimizer
    from deepspeed_tpu.runtime.engine import TrainState
    from deepspeed_tpu.runtime.lr_schedules import constant_lr
    from deepspeed_tpu.runtime.zero.planner import build_plan

    cell = json.loads((Path(__file__).parent.parent / "benchmark" / "configs"
                       / "mistral-7b-v0.3-train-l8.json").read_text())
    cfg = Config.from_dict(cell["deepspeed"])
    assert cfg.resilience.sentinel and not cfg.gradient_clipping
    plan = build_plan(topo, cfg.zero_optimization, boxed)
    engine = types.SimpleNamespace(
        config=cfg, plan=plan, mixed_precision=True, compute_dtype=BF16,
        optimizer=build_optimizer(cfg.optimizer.type, cfg.optimizer.params),
        lr_schedule=constant_lr(cfg.optimizer.params["lr"]))

    def tree(shardings, dtype):
        return jax.tree.map(lambda b, s: _sds(s, b.value.shape, dtype), boxed,
                            shardings, is_leaf=lambda l: hasattr(l, "names"))
    scalar = _sds(NamedSharding(topo.mesh, P()), (), jnp.int32)
    f32 = lambda: tree(plan.master_shardings, jnp.float32)
    state = TrainState(params=tree(plan.param_shardings, BF16), master=f32(),
                       opt_state=OptState(step=scalar, mu=f32(), nu=f32()),
                       scaler=None, global_step=scalar)
    return engine, state


def _entry_instructions(hlo_text):
    """(name, result type, opcode) of the entry computation's own
    instructions — what runs at top level, a fusion counted once."""
    import re

    entry = hlo_text[hlo_text.index("\nENTRY "):]
    return re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\(",
                      entry, re.M)


def _shapes_of(result_type):
    """Every ``dtype[dims]`` of a result type, a tuple's in order:
    ``bf16[1024,14336]{1,0:T(8,128)(2,1)}`` -> ``[("bf16", (1024, 14336))]``."""
    import re

    return [(dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", result_type)]


def test_train_step_tail_is_one_fused_pass_a_leaf(topo):
    """The tail of the train step — everything after the last gradient
    reduction — compiled for the described v5e:2x2 through the engine's own
    ``_apply_grads``: NO ``conditional`` (the skip is a select inside the
    update; a conditional's operands and results are buffers), NO top-level
    ``convert`` of a parameter shard's shape (neither the gradients'
    bf16 -> f32 nor the new master's f32 -> bf16 is a pass of its own),
    ONE fusion a leaf whose outputs are the new params (bf16), master and
    both moments (f32), and no float32 shard copied from one layout to
    another. Then the tail alone, against the parent's form
    written out (float32 gradients into a ``lax.cond`` round the update, the
    cast back after it): the float32 gradient shards are no temporaries any
    more."""
    from deepspeed_tpu.runtime import fp16 as fp16_mod
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine, _cast_tree

    layers = 2
    cell_topo, boxed, ids, grad_step = _mistral_cell(
        topo.devices, layers, "save_matmul_products")
    engine, state = _engine_tail(cell_topo, boxed)
    # the master's shards: a leaf too small to shard its params (a norm's
    # scale) still updates a quarter of itself on each chip
    shard_shapes = {s.sharding.shard_shape(s.shape)
                    for s in jax.tree.leaves(state.master)}
    n_leaves = len(jax.tree.leaves(state.params))
    shardings = jax.tree.map(lambda s: s.sharding, state)
    repl = NamedSharding(cell_topo.mesh, P())

    def tail(state, grads, loss_finite):
        # the gradients as ``engine._compute_grads`` hands them on
        grads = DeepSpeedEngine._constrain_grads(engine, grads)
        return DeepSpeedEngine._apply_grads(
            engine, state, _cast_tree(grads, jnp.float32), loss_finite)

    def train_step(state, ids):
        loss, grads = grad_step(state.params, ids)
        new_state, finite = tail(state, grads, jnp.isfinite(loss))
        return new_state, (loss, finite)

    compiled = jax.jit(train_step, out_shardings=(shardings, (repl, repl)),
                       donate_argnums=(0,)).lower(state, ids).compile()
    top = _entry_instructions(compiled.as_text())
    assert top and not [n for n, _, op in top if op == "conditional"]
    converts = [(n, t) for n, t, op in top if op == "convert"
                and _shapes_of(t)[0][1] in shard_shapes]
    assert not converts, converts

    def is_update(result_type):
        dtypes, shapes = zip(*_shapes_of(result_type) or [((), ())])
        return dtypes == ("bf16", "f32", "f32", "f32") \
            and len(set(shapes)) == 1 and shapes[0] in shard_shapes
    updates = [t for _, t, op in top if op == "fusion" and is_update(t)]
    assert len(updates) == n_leaves, (len(updates), n_leaves)
    # ... written in the state's layout: no float32 shard is copied to
    # another one (``wq``'s gradient leaves its matmul D-major, and an
    # update that took that layout copied master and both moments back)
    relaid = [(n, t) for n, t, op in top if op == "copy"
              and _shapes_of(t)[0][0] == "f32"
              and _shapes_of(t)[0][1] in shard_shapes]
    assert not relaid, relaid

    # the tail alone: this PR's against the parent's arithmetic
    def parent_tail(state, grads, loss_finite):
        grads = _cast_tree(grads, jnp.float32)
        grads = jax.lax.with_sharding_constraint(
            grads, engine.plan.grad_shardings)
        lr = engine.lr_schedule(state.opt_state.step)
        finite = fp16_mod.grads_finite(grads) & loss_finite
        new_master, new_opt = jax.lax.cond(
            finite,
            lambda op: engine.optimizer.update(grads, op[1], op[0], lr=lr),
            lambda op: op, (state.master, state.opt_state))
        return state._replace(
            params=_cast_tree(new_master, BF16), master=new_master,
            opt_state=new_opt, global_step=state.global_step + 1), finite

    flag = _sds(repl, (), jnp.bool_)
    temps = {}
    for name, fn in (("change", tail), ("parent", parent_tail)):
        alone = jax.jit(fn, out_shardings=(shardings, repl),
                        donate_argnums=(0,)).lower(
            state, state.params, flag).compile()
        temps[name] = alone.memory_analysis().temp_size_in_bytes
        has_cond = " conditional(" in alone.as_text()
        assert has_cond == (name == "parent")
    print("tail temporaries, bytes:", temps)
    f32_shards = sum(4 * int(np.prod(s.sharding.shard_shape(s.shape)))
                     for s in jax.tree.leaves(state.master))
    # the parent holds float32 gradient shards between its passes (not all
    # at once: the scheduler casts a leaf near its use); the fused tail none
    assert temps["parent"] > f32_shards // 2
    assert temps["change"] < f32_shards // 100


def test_paged_kernel_scalar_prefetch_footprint():
    """What the ragged kernel keeps in SMEM at the largest rectangle the
    benchmark serves (48 slots x 128 table columns + 1 stage page): the
    block tables, three vectors a slot, the layer index and the work list
    — ONE packed int32 a rectangle step and one spare. Compiled for the
    v5e by the case of that name above; held here under 64 KiB so that a
    second list, or an unpacked one, is a decision and not an accident."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_work_list

    S, pages, stage_rows = 48, MISTRAL["max_pages"], 8
    vec = jax.ShapeDtypeStruct((S,), jnp.int32)
    items, n_items = jax.eval_shape(
        lambda a, b, c: paged_work_list(a, b, c, block_size=128,
                                        max_pages=pages,
                                        stage_rows=stage_rows), vec, vec, vec)
    assert items.shape == (S * (pages + 1) + 1,) and n_items.shape == ()
    assert items.dtype == jnp.int32
    smem = 4 * (S * pages + 3 * S + 1 + items.shape[0])
    assert smem == 49_928 and smem < 64 * 1024


def _mistral_walk(devs, S, T, L=4, period=1):
    """``scan_layers`` — THE walk of a stacked model: a scan over depth
    (``period`` 1) or over periods of several layer kinds (place ``j`` of a
    period ropes or not) — over an ``[L, …]`` stack of Mistral-7B layers
    (bf16; the shapes are the model's own ``init``), applying the dense
    layer ``inference/forward.py`` applies less its paged attention:
    the same ``Norm``/``DenseFFN`` modules and projection einsums. Returns
    (fn, abstract args, shapes of one layer's weights)."""
    import flax.linen as nn

    from deepspeed_tpu.inference.forward import scan_layers
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.models.transformer import (DenseFFN, Norm,
                                                  apply_rope,
                                                  dense_ffn_config)
    from deepspeed_tpu.utils.annotations import device_scope

    model = build_model("mistral-7b", num_layers=1)
    m = model.config
    layer0 = nn.unbox(jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))["params"]["layer_0"])
    stack = jax.tree.map(lambda a: _sds(_one(devs), (L, *a.shape), BF16),
                         layer0)

    def layer(x, p, li, _, j=0):
        a = p["attn"]
        h = Norm(m).apply({"params": p["ln_attn"]}, x)
        with device_scope("attn_qkv"):
            q, k, v = (jnp.einsum("ste,ehd->sthd", h, a[n])
                       for n in ("wq", "wk", "wv"))
            if j:       # the kind is static: only these places rope
                pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
                q, k = apply_rope(q, k, pos, m.rope_theta)
        o = q + jnp.repeat(k + v, m.num_heads // m.num_kv_heads, axis=2)
        with device_scope("attn_out"):
            x = x + jnp.einsum("sthd,hde->ste", o, a["wo"])
        h = Norm(m).apply({"params": p["ln_ffn"]}, x)
        with device_scope("ffn"):
            f = DenseFFN(dense_ffn_config(m)).apply({"params": p["ffn"]}, h)
        return x + f, None

    def walk(stack, x):
        return scan_layers(stack, x, layer, period)[0]

    x = _sds(_one(devs), (S, T, m.hidden_size), BF16)
    return walk, (stack, x), [a.shape for a in jax.tree.leaves(layer0)]


def _top_level_outputs(hlo_text):
    """(op_name, dims, bytes) of every instruction OUTSIDE a fused
    computation: what the program writes to a buffer of its own."""
    import re

    width = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    fused, out = False, []
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            fused = "fused_computation" in head.group(1)
            continue
        ins = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if fused or not ins or not name:
            continue
        dims = tuple(int(d) for d in ins.group(2).split(",") if d)
        out.append((name.group(1), tuple(d for d in dims if d != 1),
                    width.get(ins.group(1), 4) * int(np.prod(dims))))
    return out


@pytest.mark.parametrize("S, T, period", [(48, 1, 1), (1, 128, 1),
                                          (48, 1, 4)],
                         ids=["decode_48_slots", "prefill_chunk128",
                              "decode_two_kinds_period4"])
def test_layer_walk_reads_the_stack_in_place(S, T, period, topo):
    """The scanned layer walk holds no second copy of a layer: sliced
    inside the scan body, a weight is an operand of the matmul fusion that
    consumes it. (Carried through the scan, PR 24's parent, every leaf was
    a ``dynamic-slice_bitcast_fusion`` under ``weight_walk`` — 38 % of a
    decode iteration on the chip.) What stays a buffer is the projections
    INTO heads (``[E, H, D]``: the matmul wants E in sublanes, the stack
    has H there), a ninth of the layer."""
    walk, args, leaves = _mistral_walk(topo.devices, S, T,
                                       L=4 * period, period=period)
    compiled = jax.jit(walk).lower(*args).compile()
    layer_bytes = 2 * sum(int(np.prod(s)) for s in leaves)
    # (a period's body holds ``period`` layers: each may keep its
    # projections into heads)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < layer_bytes * period
    walked = [(dims, n) for name, dims, n in
              _top_level_outputs(compiled.as_text())
              if "weight_walk" in name and n >= 1 << 20]
    ffn_shapes = {s for s in leaves if len(s) == 2}
    assert not [w for w in walked if w[0] in ffn_shapes], walked
    assert sum(n for _, n in walked) <= period * layer_bytes // 8, walked


def _prefill_step_with_its_pool_write(devs, L, halves, KV, nb, D, S=3,
                                      T=512, bs=128, H=8, block=8):
    """What a ``step_prefill`` program does to ONE pool, at a pool's real
    page geometry: the paged kernel (a custom call: it takes the pool
    row-major) reads the donated, row-major-pinned pool in every layer,
    then ``merge_step`` writes the plan's chunks by pages and the decode
    block's one token a row by rows. Returns (jitted step, abstract args,
    the pool's shape)."""
    from jax.experimental.layout import Format, Layout

    from deepspeed_tpu.inference.forward import merge_step
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_ragged_attention

    one = _one(devs)
    pinned = Format(Layout(major_to_minor=(0, 1, 2, 3, 4, 5)), one)
    latent = halves == 1
    kw = {"scale": 192 ** -0.5, "value_lanes": 512} if latent else {}

    def step(pool, q, ks, bt, sl, qs, ss, slot_map, b_slots, bk):
        outs, fresh = [], []
        for li in range(L):
            o = paged_ragged_attention(
                q, pool, ks, None if latent else ks, bt, sl, qs, ss,
                block_size=bs, layer_index=li, **kw)
            outs.append(o.astype(jnp.float32).sum())
            fresh.append(ks * (li + 1))
        k = jnp.stack(fresh)                          # [L, S, KV, T, D]
        (pool,) = merge_step((pool,), (slot_map,), (k,),
                             (None if latent else k,), T)
        (pool,) = merge_step((pool,), (b_slots,), (bk,),
                             (None if latent else bk,), 1)
        return pool, sum(outs)

    vec = _sds(one, (S,), jnp.int32)
    args = (_sds(pinned, (L, halves, KV, nb, bs, D), BF16),
            _sds(one, (S, T, H, D), BF16), _sds(one, (S, KV, T, D), BF16),
            _sds(one, (S, 8), jnp.int32), vec, vec, vec,
            _sds(one, (S, T), jnp.int32), _sds(one, (block, 1), jnp.int32),
            _sds(one, (L, block, KV, 1, D), BF16))
    fn = jax.jit(step, donate_argnums=(0,),
                 in_shardings=(pinned,) + (None,) * 9,
                 out_shardings=(pinned, None))
    return fn, args, args[0].shape


@pytest.mark.parametrize("L, halves, KV, nb, D", [
    (2, 1, 1, 64, 640), (2, 2, 4, 96, 128)],
    ids=["latent_page_640_lanes", "kv_halves_4_heads"])
def test_pool_write_compiles_with_no_pool_sized_copy(L, halves, KV, nb, D,
                                                     topo):
    """A prefill step's pool write is in place: the compiled program holds
    no ``copy`` of the pool's size. (With a read-modify-write among the
    merges — PR 55's parent: a degraded row's first page read back — the
    compiler laid the pool out for that fusion, blocks major-most, and
    copied the whole pool out of the pinned row-major layout and back:
    two copies a pool in every such program, 17 ms of a 71 ms step of the
    3.1 GiB latent pool.)"""
    from deepspeed_tpu.profiling.trace import pool_sized_copies

    fn, args, pool_shape = _prefill_step_with_its_pool_write(
        topo.devices, L, halves, KV, nb, D)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert " dynamic-update-slice(" in text
    assert pool_sized_copies(text, [pool_shape]) == []
