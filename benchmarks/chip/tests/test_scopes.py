"""The split of a trace by the round's layers (scopes.py), on a module
and events written by hand and on a small trace recorded on a TPU v5e
with the compiled module beside it (`data/small_scoped.xplane.pb.gz`,
`data/small_scoped.hlo.txt.gz`: whisper-tiny at smoke widths, W=2
co-located, compiled Pallas codec, traced by scopes.measure through the
harness's window).

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import gzip
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))

DATA = pathlib.Path(__file__).resolve().parent / "data"
SCOPED = DATA / "small_scoped.xplane.pb.gz"
SCOPED_HLO = DATA / "small_scoped.hlo.txt.gz"

HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.0 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.0 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(step)/qgadmm.codec/add"}
}

%fused_computation.1 (param_0.1: f32[8], param_1: f32[16]) -> f32[16] {
  %param_1 = f32[16]{0} parameter(1)
  %param_0.1 = f32[8]{0} parameter(0)
  %constant.1 = s32[] constant(0)
  ROOT %dynamic-update-slice.1 = f32[16]{0} dynamic-update-slice(%param_1, %param_0.1, %constant.1)
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%param_0.2), metadata={op_name="jit(step)/qgadmm.decode/neg"}
  ROOT %mul.1 = f32[8]{0} multiply(%neg.1, %param_0.2), metadata={op_name="jit(step)/qgadmm.dual/mul"}
}

%body.3 (arg.3: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.3 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.3 = f32[8]{0} get-tuple-element(%arg.3), index=1
  %wrapped_exp.3 = f32[8]{0} fusion(%get-tuple-element.3), kind=kLoop, calls=%fused_computation.2
  %i.3 = s32[] get-tuple-element(%arg.3), index=0
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%i.3, %wrapped_exp.3)
}

%cond.3 (arg.4: (s32[], f32[8])) -> pred[] {
  %arg.4 = (s32[], f32[8]{0}) parameter(0)
  %i.4 = s32[] get-tuple-element(%arg.4), index=0
  %constant.4 = s32[] constant(1)
  ROOT %lt.4 = pred[] compare(%i.4, %constant.4), direction=LT
}

ENTRY %main (p: f32[8], q: f32[16]) -> f32[16] {
  %p = f32[8]{0} parameter(0)
  %q = f32[16]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.0, metadata={op_name="jit(step)/vmap(qgadmm.local_solve)/while/body/transpose(jvp(dot_general))"}
  %dot.9 = f32[8]{0} dot(%fusion.1, %p), metadata={op_name="jit(step)/transpose(jvp(qgadmm.local_solve))/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%dot.9), kind=kLoop, calls=%fused_computation.0
  %constant_dynamic-update-slice_fusion.3 = f32[16]{0} fusion(%fusion.2, %q), kind=kLoop, calls=%fused_computation.1
  %quantize.4 = u8[16]{0} custom-call(%constant_dynamic-update-slice_fusion.3), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step)/qgadmm.codec/jit(quantize)/quantize/pallas_call"}
  %collective-permute-start.5 = (u8[16]{0}, u8[16]{0}) collective-permute-start(%quantize.4), source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(step)/qgadmm.exchange/shard_map/ppermute"}
  %fusion.6 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.0, metadata={op_name="jit(step)/jit(_threefry_split)/add"}
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2
  %tuple.8 = (s32[], f32[8]{0}) tuple(%p, %p)
  %while.8 = (s32[], f32[8]{0}) while(%tuple.8), condition=%cond.3, body=%body.3, metadata={op_name="jit(step)/qgadmm.metrics/while"}
  ROOT %out.9 = f32[16]{0} copy(%constant_dynamic-update-slice_fusion.3)
}
"""


def test_layer_map_rules():
    from chip import scopes

    layers = scopes.layer_map(HLO)
    # own op_name, also inside transforms
    assert layers["fusion.1"] == "local_solve"
    assert layers["dot.9"] == "local_solve"
    assert layers["quantize.4"] == "codec"
    assert layers["collective-permute-start.5"] == "exchange"
    assert layers["while.8"] == "metrics"
    # a fusion with no metadata: its fused computation's one token
    assert layers["fusion.2"] == "codec"
    # no metadata anywhere: the layer of its operands
    assert layers["constant_dynamic-update-slice_fusion.3"] == "codec"
    # no metadata, the fused computation carries two layers: unscoped
    assert "fusion.7" not in layers
    # metadata outside every layer scope: unscoped
    assert "fusion.6" not in layers
    # in a body the while runs, with no layer of its own: the while's
    assert layers["get-tuple-element.3"] == "metrics"
    assert "wrapped_exp.3" not in layers    # decode and dual in its body


def events():
    from chip import trace_reduce as tr

    OPS, ASYNC = tr.OPS_LINE, tr.ASYNC_LINE
    cps = ("%collective-permute-start.5 = (u8[16]{0}, u8[16]{0}) "
           "collective-permute-start(u8[16]{0} %quantize.4)")
    return {
        "host": [(0, 1000, "bench.put"), (1000, 2000, "bench.block")],
        "devices": {
            0: [
                (-100, -50, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
                 OPS),                                   # before the window
                (50, 1500, "%while.8 = (s32[], f32[8]{0}) while((s32[], "
                 "f32[8]{0}) %tuple.8)", OPS),           # control: no layer
                (100, 300, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
                 OPS),
                (250, 400, "%dot.9 = f32[8]{0} dot(f32[8]{0} %fusion.1, "
                 "f32[8]{0} %p)", OPS),                  # overlaps fusion.1
                (400, 500, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} "
                 "%dot.9)", OPS),
                (500, 600, "%constant_dynamic-update-slice_fusion.3 = "
                 "f32[16]{0} fusion(f32[8]{0} %fusion.2, f32[16]{0} %q)",
                 OPS),
                (600, 650, "%quantize.4 = u8[16]{0} custom-call(f32[16]{0} "
                 "%constant_dynamic-update-slice_fusion.3), "
                 'custom_call_target="tpu_custom_call"', OPS),
                (700, 800, "%fusion.6 = f32[8]{0} fusion(f32[8]{0} %p)",
                 OPS),
                (800, 900, "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)",
                 OPS),
                (900, 950, "%copy.99 = f32[8]{0} copy(f32[8]{0} %p)",
                 OPS),                                   # not in the module
                (1000, 1010, cps, OPS),
                (1000, 1300, cps, ASYNC),                # async span: no op
                (1600, 2500, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
                 OPS),                                   # cut at the window
            ],
            1: [(0, 100, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %dot.9)",
                 OPS)],
        },
    }


def test_reduce_by_layer():
    from chip import scopes, trace_reduce

    ev = events()
    red = scopes.reduce(ev, HLO, 1)
    ns = lambda s: round(s * 1e9, 6)
    layers = {k: ns(v) for k, v in red["layer_s"].items()}
    # local solve: [100, 400) (two overlapping ops) and [1600, 2000)
    assert layers == {"local_solve": 700, "codec": 250, "exchange": 10}
    # busy: [50, 1500) (the while spans its body) + [1600, 2000)
    assert ns(red["busy_s"]) == 1850
    assert red["busy_s"] == pytest.approx(
        trace_reduce.reduce_events(ev, 1)["busy_s"])
    # the threefry, the two-layer fusion, the op of another module, the
    # while's own time: busy that no layer covers
    assert ns(red["unscoped_s"]) == 1850 - 960
    assert sum(red["layer_s"].values()) + red["unscoped_s"] == \
        pytest.approx(red["busy_s"])
    assert ns(red["window_s"]) == 2000 and red["devices"] == 1
    # the mean over two chips: chip 1 ran 100 ns of codec
    two = scopes.reduce(ev, HLO, 2)
    assert ns(two["layer_s"]["codec"]) == (250 + 100) / 2
    assert ns(two["layer_s"]["local_solve"]) == 700 / 2
    # no compiled text (the reference system, the tests' fakes): nothing
    assert scopes.reduce(ev, None, 1) is None


@pytest.fixture(scope="module")
def recorded():
    from chip import scopes, trace_reduce

    ev = trace_reduce.load(SCOPED)
    hlo = gzip.decompress(SCOPED_HLO.read_bytes()).decode()
    return ev, hlo, scopes.reduce(ev, hlo, 1)


def test_recorded_trace_adds_up(recorded):
    from chip import trace_reduce

    ev, _, red = recorded
    busy = trace_reduce.reduce_events(ev, 1)["busy_s"]
    assert red["busy_s"] == pytest.approx(busy, rel=1e-9)
    total = sum(red["layer_s"].values()) + red["unscoped_s"]
    assert total == pytest.approx(busy, rel=1e-6)
    assert 0 <= red["unscoped_s"] < 0.05 * busy


def test_recorded_trace_reads_every_layer(recorded):
    from chip import scopes, trace_reduce

    ev, hlo, red = recorded
    ran = set()
    layers = scopes.layer_map(hlo)
    for s, e, text, line in ev["devices"][0]:
        name = trace_reduce.parse(text)[0]
        if line == trace_reduce.OPS_LINE and name in layers:
            ran.add(layers[name])
    assert {"local_solve", "codec", "decode", "metrics"} <= ran
    assert set(red["layer_s"]) == ran
    assert all(v > 0 for v in red["layer_s"].values())
