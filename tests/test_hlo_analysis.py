"""HLO collective parsing: synthetic snippets + a real jit'd module."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.hlo_analysis import (
    _shape_bytes,
    collective_stats,
    op_histogram,
    total_collective_bytes,
)

SYNTH = """\
HloModule test

%while_cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%while_body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %x = f32[4]{0} get-tuple-element(%p), index=1
  %ag = f32[8]{0} all-gather(%x), dimensions={0}
  %ar = f32[8]{0} all-reduce(%ag), to_apply=%sum
  ROOT %t = (s32[], f32[4]) tuple(%i, %x)
}

ENTRY %main (a: f32[16]) -> f32[16] {
  %a = f32[16]{0} parameter(0)
  %w = (s32[], f32[4]) while(%init), condition=%while_cond, body=%while_body
  %ag2 = bf16[32,2]{1,0} all-gather(%a2), dimensions={0}
  ROOT %r = f32[16]{0} copy(%a)
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[4]") == 16
    assert _shape_bytes("bf16[32,2]") == 128
    assert _shape_bytes("(f32[2], s32[3])") == 8 + 12


def test_loop_trip_attribution():
    stats = collective_stats(SYNTH)
    # in-loop all-gather: 7 trips x f32[8]=32B; entry all-gather bf16[32,2]=128B
    assert stats["all-gather"]["count"] == 8
    assert stats["all-gather"]["bytes"] == 7 * 32 + 128
    assert stats["all-reduce"]["count"] == 7
    # all-reduce weighted 2x in the total (ring RS+AG)
    total = total_collective_bytes(SYNTH)
    assert total == (7 * 32 + 128) + 2 * (7 * 32)


def test_real_module_collectives():
    """A psum under shard_map on a 1-device mesh still lowers an all-reduce."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("x",))

    def f(a):
        return jax.lax.psum(a, "x")

    g = jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P(),
                      check_vma=False)
    txt = jax.jit(g).lower(jnp.ones((8,))).compile().as_text()
    stats = collective_stats(txt)
    # 1-device all-reduce may be optimized away; parsing must not crash
    assert isinstance(stats, dict)


def test_op_histogram():
    h = op_histogram(SYNTH)
    assert h.get("all-gather", 0) >= 2
