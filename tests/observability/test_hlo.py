"""``observability.hlo.count_copies_of_size`` on recorded lines of
optimised HLO: the v5e compiler's output for the page pool's old
two-index scatter (PR 25: the pool arrives as ``{0,3,2,1}``, is copied
to row-major for the scatter, and copied back), and for the folded
pool's write, which holds no such copy."""

import pytest

from zookeeper_tpu.observability.hlo import count_copies_of_size

BEFORE = """\
  %copy.4 = bf16[3072,16,25,64]{3,2,1,0:T(8,128)(2,1)} copy(%buf.1), sharding={replicated}, metadata={op_name="buf"}
  ROOT %copy.6 = bf16[3072,16,25,64]{0,3,2,1:T(8,128)(2,1)} copy(%fusion), metadata={op_name="jit(two_idx)/scatter"}
"""
AFTER = """\
  %copy.9 = bf16[48,25,64]{2,1,0:T(8,128)(2,1)S(1)} copy(%k.1), sharding={replicated}, metadata={op_name="k"}
  ROOT %fusion = bf16[3072,1,16,1664]{3,2,1,0:T(8,128)(2,1)} fusion(%buf.1, %fusion.4, %copy.9), kind=kCustom, calls=%fused_computation
"""
ASYNC = """\
  %copy-start.1 = (bf16[3072,16,25,64]{3,2,1,0:T(8,128)(2,1)}, bf16[3072,16,25,64]{0,3,2,1:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%buf.1)
  %transpose.3 = bf16[16,3072,25,64]{3,2,1,0:T(8,128)(2,1)} transpose(%buf.2), dimensions={1,0,2,3}
"""
#: The v5e compiler's prefetch of a 2 MB token table into fast memory
#: (``chip_smoke.py``'s decode step, PR 32): a move between memory
#: spaces in the layout the table has, not a re-layout.
PREFETCH = """\
  %copy-start.3 = (f32[1024,512]{1,0:T(8,128)S(1)}, f32[1024,512]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%variables__params____embed__.1)
"""
#: The same compiler's re-layout of GPT-2 XL's token table before the
#: gather, which PR 32 took out.
RELAYOUT = """\
  %copy.8 = f32[50257,1600]{1,0:T(8,128)} copy(%variables__params____embed__.1)
"""
OLD_LEAF = 3072 * 16 * 25 * 64
NEW_LEAF = 3072 * 1 * 16 * 1664


@pytest.mark.parametrize(
    "text, count",
    [(BEFORE, 2), (AFTER, 0), (ASYNC, 2), (BEFORE + AFTER + ASYNC, 4)],
    ids=["two_index_scatter", "folded_write", "async_and_transpose", "all"],
)
def test_count_copies_of_size(text, count):
    assert count_copies_of_size(text, [OLD_LEAF, NEW_LEAF]) == count


def test_count_copies_of_size_ignores_moves_to_fast_memory():
    assert count_copies_of_size(PREFETCH, [1024 * 512]) == 0
    assert count_copies_of_size(RELAYOUT, [50257 * 1600]) == 1
    assert count_copies_of_size(AFTER, [48 * 25 * 64]) == 0


def test_count_copies_of_size_ignores_other_sizes():
    assert count_copies_of_size(BEFORE, [NEW_LEAF]) == 0
    assert count_copies_of_size(BEFORE, []) == 0
