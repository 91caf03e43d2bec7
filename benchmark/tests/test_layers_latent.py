"""The readers of a decoder of latent attention on a hand-made context
(what the traced serving run hands over) and on a synthetic scope table:
numbers where the program publishes what they read — the latent rows as
NEEDED (1,152 B a token a layer at the published widths), the experts of
the layers that HAVE them — and None, not an exception, where it does not
(a parent commit, another model)."""
import pytest

from benchmark import common, work_latent
from benchmark.layers import (_scopes, decode_latent_absorb_share,
                              latent_attn_roofline, latent_gmm_roofline)

#: one leading dense layer, four expert layers; every layer attends
CFG = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 24,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "num_hidden_layers": 5, "first_k_dense_replace": 1,
       "vocab_size": 100, "n_routed_experts": 8, "num_experts_per_tok": 2,
       "n_shared_experts": 2}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def ctx(by_program, model=CFG, **stats):
    st = {"window_iters": 8, "decode_steps": 2, **stats}
    return {"trace": {"ops_by_program": by_program, "programs": {}},
            "stats": st, "model": model, "peaks": PEAKS, "tokens_emitted": 40,
            "progress": ({7: (100, 100), 8: (30, 12)},
                         {7: (100, 106), 8: (30, 30)}),
            "uid_of": {}, "done_len": {}}


def test_attn_roofline_reads_the_latent_rows_as_needed(capsys):
    # five layers: 50 calls in 10 iterations. uid 7 decoded from 100 to 106
    # tokens; uid 8 is still inside its prompt (12 of 30)
    c = ctx({"jit_run": {"paged_latent_decode": [0.004, 40.0]},
             "jit_step_decode": {"paged_latent_decode.1": [0.001, 10.0]},
             "jit_step_prefill": {"paged_latent_prefill": [9.0, 4.0],
                                  "paged_latent_decode": [7.0, 4.0]}})
    span = work_latent.latent_decode_span(CFG, 100, 106)
    # 6 steps over contexts 101..106, 5 layers, 32 values x 2 B a token
    assert span["bytes"] == 5 * 64 * sum(range(101, 107))
    least = max(span["flops"] / 1e12, span["bytes"] / 1e9)
    assert latent_attn_roofline.read(c) == pytest.approx(100 * least / 0.005)
    # one call an iteration is another model's walk (one attention layer)
    few = ctx({"jit_run": {"paged_latent_decode": [0.004, 10.0]}})
    assert latent_attn_roofline.read(few) is None
    assert "KERNEL NAME AMBIGUOUS" in capsys.readouterr().out
    # the K/V kernel's name, or a model without a latent: nothing to read
    assert latent_attn_roofline.read(
        ctx({"jit_run": {"paged_attn_decode": [0.004, 50.0]}})) is None
    other = {k: v for k, v in CFG.items() if k != "kv_lora_rank"}
    assert latent_attn_roofline.read(
        ctx({"jit_run": {"paged_latent_decode": [0.004, 50.0]}},
            model=other)) is None


def test_gmm_roofline_counts_the_four_expert_layers(capsys):
    # 10 decode iterations x 4 expert layers x 3 GEMMs = 120 calls; 40
    # tokens -> a mean batch of 4 live rows a step
    c = ctx({"jit_run": {"grouped_matmul_fwd": [0.008, 96.0]},
             "jit_step_decode": {"grouped_matmul_fwd.1": [0.002, 24.0]},
             "jit_step_prefill": {"grouped_matmul_fwd": [9.0, 6.0]}})
    touched = work_latent.experts_touched_uniform(CFG, 4.0)
    one = work_latent.grouped_matmul(CFG, 8.0, touched)
    least = max(one["flops"] * 40 / 1e12, one["bytes"] * 40 / 1e9)
    assert latent_gmm_roofline.read(c) == pytest.approx(100 * least / 0.010)
    # tokens a prefill step's decode block made are in no decode iteration:
    # 8 of the 40 leave a mean batch of 3.2 rows
    c["stats"]["fused_decode_tokens"] = 8
    touched = work_latent.experts_touched_uniform(CFG, 3.2)
    one = work_latent.grouped_matmul(CFG, 6.4, touched)
    least = max(one["flops"] * 40 / 1e12, one["bytes"] * 40 / 1e9)
    assert latent_gmm_roofline.read(c) == pytest.approx(100 * least / 0.010)
    # 150 calls = 3 x FIVE layers x 10 iterations: not this stack's
    assert latent_gmm_roofline.read(
        ctx({"jit_run": {"grouped_matmul_fwd": [0.004, 300.0]}})) is None
    assert "KERNEL NAME AMBIGUOUS" in capsys.readouterr().out


def test_absorb_share_from_a_scope_table(monkeypatch):
    table = {"jit_run": {("moe_experts", "fwd"): 8.0,
                         ("latent_absorb", "fwd"): 0.5,
                         ("attn_core", "fwd"): 1.0},
             "jit_step_decode": {("latent_absorb", "fwd"): 0.5},
             "jit_step_prefill": {("latent_absorb", "fwd"): 100.0}}
    monkeypatch.setattr(_scopes, "table", lambda ctx: table)
    assert decode_latent_absorb_share.read({}) == pytest.approx(10.0)
    # a program without the scope (a parent commit, another model): nothing
    # to read, not 0 %
    monkeypatch.setattr(_scopes, "table", lambda ctx: {
        "jit_run": {("ffn", "fwd"): 1.0}})
    assert decode_latent_absorb_share.read({}) is None
    monkeypatch.setattr(_scopes, "table", lambda ctx: None)
    assert decode_latent_absorb_share.read({}) is None


def test_read_layers_leaves_out_what_a_parent_cannot_give():
    entry = {"metrics": {"per_layer": [
        {"name": n, "unit": "%"} for n in (
            "latent_attn_roofline", "decode_latent_absorb_share",
            "latent_gmm_roofline")]}}
    parent = ctx({"jit_run": {"fusion": [0.1, 3.0]}})
    parent["trace"]["host_only"] = True
    assert common.read_layers(entry, parent) == {}
