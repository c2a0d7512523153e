"""The serving programs name their parts (docs/OBSERVABILITY.md "Named
scopes"): the model's ``mx.embed`` / ``mx.norm`` / ``mx.attn`` / ``mx.ffn``
/ ``mx.ssm`` / ``mx.ssm_state`` / ``mx.head`` and the engine's own
``mx.attend`` / ``mx.kv_write`` / ``mx.sample``, read back per program by
``InferenceEngine.scope_table``.

On the CPU the tables of the programs that ran must hold every scope the
family has, and reading one must leave the engine's trace counters as they
were. The same programs compiled for a described v5e are held to their
parts in ``test_pallas_attention.py`` (the one file that describes the
chip): every instruction that holds a product booked to a part that owns
weights, the kernels under ``mx.attend`` and ``mx.ssm_state``, the weights'
copies into the fast memory to the part that reads them.
"""

import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serve import InferenceEngine, Request

GPT_SCOPES = {"mx.embed", "mx.norm", "mx.attn", "mx.attend", "mx.kv_write",
              "mx.ffn", "mx.head", "mx.sample"}
HYBRID_SCOPES = GPT_SCOPES | {"mx.ssm", "mx.ssm_state"}
WEIGHT_PARTS = {"mx.attn", "mx.ffn", "mx.ssm", "mx.head"}


def _gpt_engine():
    # GPT-2-XL's 25 heads of 64 and 64 slots: at that many rows a step the
    # chip's compiler rebuilds the page write's scatter without a name
    model = GPTModel(vocab_size=256, units=1600, hidden_size=256,
                     num_layers=2, num_heads=25, max_length=128,
                     dtype="bfloat16")
    model.initialize()
    return InferenceEngine(model, num_slots=64, page_size=16, max_len=128,
                           num_pages=33, chunk_pages=2,
                           token_budget=32), (1,)


def _hybrid_engine():
    from incubator_mxnet_tpu.models.granite_hybrid import \
        granite_hybrid_mini
    # heads of 64 and a state of 128, as the kernels' tiles want them
    model = granite_hybrid_mini(
        vocab_size=256, layer_types=["mamba", "attention"], units=256,
        num_heads=4, num_kv_heads=2, head_dim=64, ssm_heads=2,
        ssm_head_dim=64, ssm_state=128, dtype="bfloat16")
    model.initialize()
    return InferenceEngine(model, num_slots=4, page_size=16, max_len=128,
                           prefix_cache=False, chunk_pages=2,
                           token_budget=32), (1, 17)


FAMILIES = {"gpt": (_gpt_engine, GPT_SCOPES),
            "hybrid": (_hybrid_engine, HYBRID_SCOPES)}


def served_engine(family):
    """(engine, donated arguments, scopes) of ``family`` after one request:
    a chunk of 32 positions, one of 16 and decode steps have run."""
    build, scopes = FAMILIES[family]
    eng, donate = build()
    eng.run([Request(np.arange(1, 41, dtype=np.int32), max_new_tokens=3,
                     temperature=0.0)])
    return eng, donate, scopes


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request):
    return (request.param,) + served_engine(request.param)


# --------------------------------------------------------------- the CPU

def test_tables_of_the_programs_that_ran_hold_every_scope(served):
    _, eng, _, scopes = served
    assert {"decode", ("chunk", 32), ("chunk", 16)} <= set(eng._programs)
    for name in ("decode", ("chunk", 32)):
        table = eng.scope_table(name)
        got = {sc for sc, _ in table.values() if sc}
        assert got == scopes, (name, scopes ^ got)
        # the compiler's nameless copies go to their readers' part
        assert table == profiler.scope_table(
            eng.compiled_program_text(name), fill_unnamed=True)


def test_reading_a_table_leaves_the_trace_counters(served):
    _, eng, _, _ = served
    counts = (eng.decode_trace_count, eng.verify_trace_count,
              eng.prefill_trace_count, dict(eng.prefill_trace_counts))
    eng.scope_table("decode")
    eng.scope_table(("chunk", 16))
    assert (eng.decode_trace_count, eng.verify_trace_count,
            eng.prefill_trace_count, dict(eng.prefill_trace_counts)) \
        == counts
    assert counts[0] == 1
    with pytest.raises(Exception, match="no program"):
        eng.scope_table(("dense", 64))


def test_fill_unnamed_books_what_the_compiler_made_to_its_part():
    """A weight's asynchronous copy into fast memory and its relayout carry
    no name of their own; they take their reader's. Readers that disagree
    leave it unscoped. A fusion whose root the compiler rebuilt without a
    name (the page write's scatter) takes the one scope inside it, before
    its reader's. Without ``fill_unnamed`` nothing moves."""
    text = """HloModule m

%s (p0: bf16[8,8], p1: s32[2], p2: bf16[2,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %p1 = s32[2]{0} parameter(1)
  %p2 = bf16[2,8]{1,0} parameter(2)
  %reshape.5 = bf16[2,1,8]{2,1,0} reshape(%p2), metadata={op_name="jit(s)/mx.attn/mx.kv_write/reshape"}
  ROOT %scatter.6 = bf16[8,8]{1,0} scatter(%p0, %p1, %reshape.5), update_window_dims={2}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%r
}

ENTRY %main (w: bf16[8,8], x: bf16[8,8], pool: bf16[8,8], i: s32[2]) -> bf16[8,8] {
  %w = bf16[8,8]{1,0} parameter(0), metadata={op_name="w"}
  %x = bf16[8,8]{1,0} parameter(1)
  %pool = bf16[8,8]{1,0} parameter(2)
  %i = s32[2]{0} parameter(3)
  %copy-start = (bf16[8,8]{1,0}, bf16[8,8]{1,0}, u32[]) copy-start(%w)
  %copy-done = bf16[8,8]{1,0} copy-done(%copy-start)
  %copy.1 = bf16[8,8]{0,1} copy(%copy-done)
  %fusion.2 = bf16[8,8]{1,0} fusion(%copy.1, %x), kind=kOutput, calls=%f, metadata={op_name="jit(s)/mx.ffn/dot_general"}
  %fusion.3 = bf16[8,8]{1,0} fusion(%x), kind=kLoop, calls=%g, metadata={op_name="jit(s)/mx.norm/mul"}
  %fusion.4 = bf16[8,8]{1,0} fusion(%x), kind=kLoop, calls=%g, metadata={op_name="jit(s)/mx.sample/max"}
  %fusion.7 = bf16[8,8]{1,0} fusion(%pool, %i, %fusion.2), kind=kCustom, calls=%s
  %custom-call.8 = bf16[8,8]{1,0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/mx.attn/mx.attend/pallas_call"}
  ROOT %tuple = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) tuple(%custom-call.8, %fusion.3)
}
"""
    plain = profiler.scope_table(text)
    assert plain["copy-done"] == ("", "") and plain["w"] == ("", "")
    assert plain["fusion.7"] == ("", "")
    booked = profiler.scope_table(text, fill_unnamed=True)
    for name in ("copy-start", "copy-done", "copy.1"):
        assert booked[name] == ("mx.ffn", ""), name
    # w is read by the copy only: mx.ffn; x by three parts that disagree
    assert booked["w"] == ("mx.ffn", "")
    assert booked["x"] == ("", "")
    assert booked["tuple"] == ("", "")
    # the scatter's fusion: what lies inside it, not the kernel reading it
    assert booked["fusion.7"] == ("mx.kv_write", "")
    # its operands: the pool and indices read by the write alone
    assert booked["pool"] == booked["i"] == ("mx.kv_write", "")
    assert {k: v for k, v in booked.items() if v != plain[k]} == dict(
        {n: ("mx.ffn", "") for n in ("w", "copy-start", "copy-done",
                                     "copy.1")},
        **{n: ("mx.kv_write", "") for n in ("fusion.7", "pool", "i",
                                            "p2")})
