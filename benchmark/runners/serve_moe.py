"""Serving runner for a ``mode: serve_moe`` configuration: a decoder whose
feed-forward layers are routed experts (OLMoE). Everything but the
reference check is ``runners/serve.py`` — the Router over one worker for
``--trace 0``, the engine in process for ``--trace 1``, the same load loop,
judge and result line. The check keeps ``serve.reference_check``'s
contract (a seeded sample of served streams, teacher-forced; the first
token is the prefill form's, the later ones the decode form's; the served
token's reference logit within ``logit_tolerance`` of the maximum) and
takes the plain reference from the module the cell's ``reference`` section
names. It also counts how many (token, layer) top-k expert sets move when
the reference's own hidden state is rounded to bfloat16 after every block:
the near-tie swaps a bf16 server makes against float32, which is what the
tolerance has to leave room for; and the share of checked rows whose served
token is not the reference's argmax at all (reported, not judged).
"""
from __future__ import annotations

import importlib
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.runners import serve  # noqa: E402


def reference_margins(ref, model_cfg, params, streams, spec: dict) -> dict:
    """``streams``: [(prompt tokens, served tokens)]. Worst margin (the
    reference's maximum logit less its logit of the served token) of the
    first served token and of the later ones, and the share of (token,
    layer) top-k sets that a bf16 rounding of the hidden state moves."""
    import jax.numpy as jnp

    m = model_cfg
    bucket = int(spec.get("pad_to", 256))
    worst = {"prefill_form": 0.0, "decode_form": 0.0}
    rows_checked = sets = moved = off_argmax = 0
    for prompt, served in streams:
        P, n = len(prompt), min(len(served), int(spec["rows"]))
        toks = list(prompt) + list(served)
        S = -(-len(toks) // bucket) * bucket
        padded = np.zeros(S, np.int32)
        padded[:len(toks)] = toks
        rows = np.arange(P - 1, P - 1 + n)
        kw = dict(embed=params["embed"],
                  layer=lambda i: ref.program_layer(params, i),
                  num_layers=m.num_layers,
                  ln_final=params["ln_final"]["scale"],
                  unembed=params["unembed"], theta=float(m.rope_theta),
                  eps=float(m.norm_eps), top_k=m.moe.top_k,
                  renormalise=bool(m.moe.normalize_gates), rows=rows)
        exact, rounded = [], []
        logits = np.asarray(ref.forward_logits(padded, routes=exact, **kw))
        ref.forward_logits(padded, routes=rounded,
                           round_hidden=jnp.bfloat16, **kw)
        for a, b in zip(exact, rounded):
            a = np.sort(np.asarray(a)[:len(toks)], axis=-1)
            b = np.sort(np.asarray(b)[:len(toks)], axis=-1)
            sets += len(a)
            moved += int((a != b).any(axis=-1).sum())
        tok = np.asarray(served[:n])
        margin = logits.max(axis=1) - logits[np.arange(n), tok]
        worst["prefill_form"] = max(worst["prefill_form"], float(margin[0]))
        if n > 1:
            worst["decode_form"] = max(worst["decode_form"],
                                       float(margin[1:].max()))
        rows_checked += n
        off_argmax += int((margin > 0).sum())
    return {"requests": len(streams), "rows": rows_checked,
            "worst_margin": worst,
            "rows_off_the_reference_argmax_share":
                off_argmax / max(rows_checked, 1),
            "topk_sets_moved_by_bf16_share": moved / max(sets, 1),
            "topk_sets": sets}


def seeded_params(conf: dict, seed: int):
    """The same seeded weights the worker built: same initialiser, same
    key, same cast (bf16) — held as they are served; the reference casts
    them up a layer at a time."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.weights import load_tp_params
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.parallel.topology import MeshConfig, MeshTopology

    model = build_model(conf["preset"], **conf["overrides"])
    topo = MeshTopology(MeshConfig(tensor=1, data=1),
                        devices=jax.devices()[:1])
    params, _ = load_tp_params(model, None, jax.random.PRNGKey(seed), topo,
                               jnp.bfloat16)
    return model.config, params


def reference_check(ok, conf: dict, cellp: dict, seed: int):
    """``serve.reference_check``'s contract, with the cell's own reference
    module. Runs in THIS process, on the device the worker has given
    back."""
    spec = cellp["reference"]
    ref = importlib.import_module(spec["module"])
    fits = [r for r in ok if len(r.prompt) + r.max_new <= spec["max_tokens"]]
    rng = np.random.default_rng([seed, 11])
    rng.shuffle(fits)
    sample = fits[:int(spec["requests"])]
    if len(sample) < int(spec["requests"]):
        return False, {"error": f"only {len(sample)} served streams fit the "
                                f"reference's {spec['max_tokens']} tokens"}
    model_cfg, params = seeded_params(conf, seed)
    detail = reference_margins(ref, model_cfg, params,
                               [(r.prompt, r.tokens) for r in sample], spec)
    tol = float(spec["logit_tolerance"])
    detail["tolerance"] = tol
    return max(detail["worst_margin"].values()) <= tol, detail


serve.reference_check = reference_check

if __name__ == "__main__":
    sys.exit(serve.main())
