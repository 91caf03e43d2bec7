#!/usr/bin/env python3
"""Records the small SCOPED device trace kept in ``tests/data/`` — run by
hand on the chip (``chiprun -- python3 benchmark/tests/
record_scope_fixture.py``), never by a test.

Two jitted programs carrying scopes of ``DEVICE_SCOPES``, registered with the
program's own registry (``profiling.trace.register_program``):

- ``fx_decode``: a layer scan whose weights ride the carry (``weight_walk``),
  a NAMED Pallas kernel under ``attn_core``, matmuls under ``ffn`` and
  ``head``. Jitted TWICE, one jit a batch size as the serving engine
  holds its programs, so two compiled programs share the module name
  ``jit_fx_decode`` and their maps are merged;
- ``fx_train``: value_and_grad over two rematted flax-style layers
  (``layer_N/attn``, ``layer_N/ffn``), ``head_loss`` and an ``optimizer``
  update: forward, backward and recomputation in one program.

Beside the trace it writes what the program's maps said
(``program_scope_maps()``), the scope table ``layers/_scopes.py`` joined from
them, ``reduce_trace.summarize`` of the same window, and what the "XLA
Modules" and "XLA Ops" lines print for a program and for the named kernel —
the facts the readers were built on."""
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

NAME = "tpu_v5e_scopes"
KERNEL = "fx_paged_attn_decode"


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from benchmark import reduce_trace
    from benchmark.layers import _scopes
    from deepspeed_tpu.ops.pallas import interpret_mode
    from deepspeed_tpu.profiling import trace as ptrace
    from deepspeed_tpu.utils.annotations import device_scope

    out = os.path.join("chiprun_out", "benchmark", "scope_fixture")
    shutil.rmtree(out, ignore_errors=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}")
        return 1
    E, L = 128, 3
    bf16 = jnp.bfloat16

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 0.5

    def fx_decode(w, x):
        def take(i):
            with device_scope("weight_walk"):
                return jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)

        def body(carry, i):
            h, w_cur = carry
            w_next = take(jnp.minimum(i + 1, L - 1))
            with device_scope("attn_core"):
                a = pl.pallas_call(
                    kernel, out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
                    name=KERNEL, interpret=interpret_mode())(h)
            with device_scope("ffn"):
                h = h + jnp.tanh(a @ w_cur)
            return (h, w_next), None

        (h, _), _ = jax.lax.scan(body, (x, take(0)), jnp.arange(L))
        with device_scope("head"):
            return jnp.argmax(h @ w[0], axis=-1)

    def fx_train(params, x):
        def block(p, h):
            with jax.named_scope("attn"):
                h = h + jnp.tanh(h @ p["a"])
            with jax.named_scope("ffn"):
                return h + jax.nn.gelu(h @ p["f"])

        def loss(params):
            h = x
            for i in range(2):
                with jax.named_scope(f"layer_{i}"):
                    h = jax.checkpoint(block)(params[f"layer_{i}"], h)
            with device_scope("head_loss"):
                return jnp.mean(jnp.square(h.astype(jnp.float32)))

        val, grads = jax.value_and_grad(loss)(params)
        with device_scope("optimizer"):
            params = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype),
                                  params, grads)
        return params, val

    xs = [jnp.ones((rows, E), bf16) for rows in (64, 128)]
    decode = [ptrace.register_program(jax.jit(fx_decode)) for _ in xs]
    train = ptrace.register_program(jax.jit(fx_train))
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (L, E, E), bf16) * 0.05
    params = {f"layer_{i}": {"a": w[i], "f": w[i + 1]} for i in range(2)}
    xt = jnp.ones((256, E), bf16)
    jax.block_until_ready([d(w, x) for d, x in zip(decode, xs)]
                          + [train(params, xt)])

    reduce_trace.start(out)
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(reduce_trace.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("dispatch"):
                y = [d(w, x) for d, x in zip(decode, xs)]
                params, val = train(params, xt)
            jax.block_until_ready((y, val))
            with jax.profiler.TraceAnnotation("plan"):
                time.sleep(0.002)
    wall = time.monotonic() - t0
    summary = reduce_trace.stop_and_summarize(out)
    src = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    pb = os.path.join(out, NAME + ".xplane.pb")
    shutil.copy(src, pb)

    t0 = time.monotonic()
    maps = ptrace.program_scope_maps()
    maps_s = time.monotonic() - t0
    planes = reduce_trace.load(pb)
    table = _scopes.join(planes, maps, ptrace.scope_of)
    dev0 = reduce_trace.device_planes(planes)[0]
    lines = {ln["name"]: ln["events"] for ln in dev0["lines"]}
    modules = sorted({e[0] for e in lines[reduce_trace.MODULES_LINE]})
    kernel_ops = sorted({e[0][:160] for e in lines[reduce_trace.OPS_LINE]
                         if KERNEL in e[0] or "closed_call" in e[0]})
    # SELF time by op over the WHOLE trace (no window), for the program's
    # own reader (profiling.trace.op_breakdown) to be held to
    whole: dict = {}
    for p in reduce_trace.device_planes(planes):
        for ln in p["lines"]:
            if ln["name"] == reduce_trace.OPS_LINE:
                for name, _, _, self_ns in reduce_trace.self_times(
                        ln["events"]):
                    k = reduce_trace.op_key(name)
                    whole[k] = whole.get(k, 0.0) + self_ns / 1e6
    # what could the number after a module's name be? the candidates an
    # executable exposes, for the reader of this file to compare
    ids = {}
    for prog in (*decode, train):
        args, kwargs = prog.avals
        ex = prog.fn.lower(*args, **kwargs).compile().runtime_executable()
        fp = getattr(ex, "fingerprint", None)
        ids.setdefault(prog.module_name, []).append(
            fp.hex() if isinstance(fp, bytes) else repr(fp))
    expected = {
        "host_wall_s": wall, "runs_each": 2, "kernel": KERNEL, "layers": L,
        "ops_ms_whole_trace": whole,
        "modules_line": modules, "kernel_ops_line": kernel_ops,
        "executable_ids": ids, "maps_seconds": maps_s,
        "maps": maps, "summary": summary,
        "table": {p: [[s, d, v] for (s, d), v in t.items()]
                  for p, t in table.items()},
        "check": _scopes.check(table, summary["ops_by_program"]),
    }
    with open(os.path.join(out, NAME + ".expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    _scopes.show(table)
    print(json.dumps({k: expected[k] for k in (
        "modules_line", "kernel_ops_line", "executable_ids", "maps_seconds",
        "check")}, indent=1))
    print({m: (v["programs"], v["hlo_bytes"],
               sum(o == "ambiguous" for o in v["ops"].values()),
               len(v["ops"])) for m, v in maps.items()})
    print("ops_by_program:", json.dumps(summary["ops_by_program"])[:3000])
    print("bytes:", os.path.getsize(pb))
    return 0


if __name__ == "__main__":
    sys.exit(main())
