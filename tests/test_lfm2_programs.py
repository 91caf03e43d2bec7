"""Tracing of the record kind (``conv_mix``, ``state_commit``) in the
compiled programs: the scopes land in LFM2-MoE's prefill step, decode step
and decode window — and in no other served family's (Tentpole F of ISSUE 50
in the suite: a model without conv layers carries an EMPTY record tuple)."""
import jax
import pytest


# ---------------------------------------------------------------------------
# tracing: the new scopes land in this model's programs and in no other's
# ---------------------------------------------------------------------------

def _programs_of(preset, **over):
    """{module: (scope counter, the program's abstract pools)} of a tiny
    engine's own compiled programs after a short generation (prefill
    chunks, a decode step, decode windows)."""
    import collections
    import gc

    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.profiling import trace as ptrace

    eng = InferenceEngineV2(
        build_model(preset, **over), rng=jax.random.PRNGKey(6),
        config={"block_size": 8, "num_blocks": 64, "max_seqs": 2, "chunk": 8,
                "max_seq_len": 128})
    eng.generate([list(range(5, 24)), [3, 4]], max_new_tokens=6)
    found = {}
    for prog in eng._programs.values():
        if getattr(prog, "avals", None) is not None:
            parsed = prog.scopes()
            scopes, pools = found.setdefault(
                parsed["module"], (collections.Counter(), []))
            scopes.update(ptrace.scope_of(op)
                          for op in parsed["ops"].values())
            pools.append(prog.avals[0][1])
    kinds = eng._kinds
    del eng, prog
    gc.collect()
    return found, kinds


MODULES = {"jit_step_prefill", "jit_step_decode", "jit_run"}


def test_the_record_scopes_land_in_this_models_programs():
    found, kinds = _programs_of("tiny-lfm2-moe")
    assert MODULES <= set(found)
    for mod in MODULES:
        scopes, pools = found[mod]
        assert scopes[("conv_mix", "fwd")] > 0, (mod, dict(scopes))
        assert scopes[("state_commit", "fwd")] > 0, (mod, dict(scopes))
        # one paged pool (the one attention layer's) and the records
        assert all([len(p.shape) for p in ps] == [6, 4] for ps in pools)
    # the attention scopes belong to the one attention layer
    assert found["jit_run"][0][("attn_core", "fwd")] > 0
    assert [len(k.layers) for k in kinds] == [1, 4]


@pytest.mark.parametrize("preset, over", [
    ("tiny-llama", {"sliding_window": 64}),        # the Mistral family
    ("tiny-olmoe", {}), ("tiny-smallthinker", {})])
def test_no_other_models_program_carries_a_record(preset, over):
    """Tentpole F in the suite: the prefill step, the decode step and the
    decode window of the three served families hold no ``conv_mix`` /
    ``state_commit`` scope and no record operand: a model without conv
    layers carries an EMPTY record tuple, not a dummy array."""
    found, kinds = _programs_of(preset, **over)
    assert MODULES <= set(found)
    assert not any(k.is_record for k in kinds)
    for mod in MODULES:
        scopes, pools = found[mod]
        assert scopes[("conv_mix", "fwd")] == 0
        assert scopes[("state_commit", "fwd")] == 0
        for ps in pools:
            assert len(ps) == len(kinds)
            assert all(len(p.shape) == 6 for p in ps)      # pages, all
