"""Collective parsing on synthetic HLO: the bytes and op counts the dry
run records from a compiled program."""
from repro.roofline.collectives import (
    collective_bytes_from_hlo, collective_op_counts,
)

HLO = """
ENTRY main {
  %x = bf16[4,1024,128]{2,1,0} parameter(0)
  %ag = bf16[64,1024,128]{2,1,0} all-gather(%x), replica_groups={}
  %ar = f32[512,512]{1,0} all-reduce(%y), to_apply=%add
  %rs = f32[32,512]{1,0} reduce-scatter(%z), to_apply=%add
  %aa = s8[1024,64]{1,0} all-to-all(%w)
  %cp = bf16[16,16]{1,0} collective-permute(%v)
  %ag2s = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-gather-start(%q)
  %nothing = bf16[999,999]{1,0} add(%x, %x)
}
"""


def test_collective_bytes_sums_outputs():
    want = (64 * 1024 * 128 * 2      # all-gather bf16
            + 512 * 512 * 4          # all-reduce f32
            + 32 * 512 * 4           # reduce-scatter
            + 1024 * 64 * 1          # all-to-all s8
            + 16 * 16 * 2            # collective-permute
            + 8 * 8 * 2 * 2)         # async start tuple
    assert collective_bytes_from_hlo(HLO) == want


def test_collective_op_counts():
    counts = collective_op_counts(HLO)
    assert counts["all-gather"] == 2
    assert counts["all-reduce"] == 1
    assert "add" not in counts
