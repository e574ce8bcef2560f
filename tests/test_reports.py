"""Reporting layer: dryrun table, collective parser."""

from repro.launch.dryrun import _group_size, _shape_bytes, parse_collectives

HLO_SNIPPET = """
  %ar = f32[16,1024]{1,0} all-reduce(f32[16,1024]{1,0} %x), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %ag = bf16[32,512]{1,0} all-gather(bf16[2,512]{1,0} %y), replica_groups=[2,16]<=[32], dimensions={0}
  %cp = bf16[8,128]{1,0} collective-permute(bf16[8,128]{1,0} %z), source_target_pairs={{0,1},{1,0}}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[16,1024]{1,0}") == 16 * 1024 * 4
    assert _shape_bytes("bf16[2,512]") == 2 * 512 * 2
    assert _shape_bytes("(f32[4], bf16[8])") == 16 + 16


def test_group_size_formats():
    assert _group_size("replica_groups={{0,1,2,3},{4,5,6,7}}") == 4
    assert _group_size("replica_groups=[2,16]<=[32]") == 16


def test_parse_collectives_kinds_and_wire():
    out = parse_collectives(HLO_SNIPPET)
    assert out["all-reduce"]["count"] == 1
    assert out["all-gather"]["count"] == 1
    assert out["collective-permute"]["count"] == 1
    # all-reduce ring wire = 2 * bytes * (n-1)/n
    b = 16 * 1024 * 4
    assert abs(out["all-reduce"]["wire_bytes"] - 2 * b * 3 / 4) < 1
    assert out["__top_ops__"][0]["kind"] == "all-reduce"
