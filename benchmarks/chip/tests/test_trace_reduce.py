"""The trace reduction, on events made by hand and on a small trace
recorded on a TPU v5e (`data/small.xplane.pb.gz`: three rounds of whisper-tiny
at smoke widths, W=2 co-located, compiled Pallas codec, traced with the
harness's host annotations).

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))

SMALL = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb.gz"


def test_union_overlap_and_exposed_collectives():
    from chip import trace_reduce as tr

    OPS, ASYNC = tr.OPS_LINE, tr.ASYNC_LINE
    ev = {
        "host": [(0, 1000, "bench.put"), (1000, 2000, "bench.block")],
        "devices": {0: [
            (100, 300, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", OPS),
            (200, 400, '%quantize.2 = u8[4,128]{1,0} custom-call(f32[1,1] %r),'
                       ' custom_call_target="tpu_custom_call"', OPS),
            (90, 1200, "%while.5 = (s32[]) while((s32[]) %t)", OPS),
            (500, 520, "%collective-permute-start.1 = u8[8]{0} "
                       "collective-permute-start(u8[8]{0} %w)", OPS),
            (500, 700, "%collective-permute-start.1 = u8[8]{0} "
                       "collective-permute-start(u8[8]{0} %w)", ASYNC),
            (690, 700, "%collective-permute-done.1 = u8[8]{0} "
                       "collective-permute-done(u8[8]{0} %c)", OPS),
            (600, 650, "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %q)", OPS),
            (1500, 2500, "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %x)", OPS),
        ]},
    }
    red = tr.reduce_events(ev, 1)
    assert red["window_s"] == pytest.approx(2000e-9)
    # busy: [90, 1200) (the while spans its body) + [1500, 2000)
    assert red["busy_s"] == pytest.approx(1610e-9)
    assert red["kernel_s"]["quantize"] == pytest.approx(200e-9)
    assert red["collective_s"] == pytest.approx(200e-9)
    assert red["collective_exposed_s"] == pytest.approx(150e-9)
    gaps = dict((n, s) for n, s in red["breakdown"]["idle_gaps"])
    assert gaps["bench.block"] == pytest.approx(300e-9)   # [1200, 1500)
    top = [name for name, _ in red["breakdown"]["device_ops"]]
    assert top[0].startswith("fusion.4 fusion")
    assert not any(name.startswith("while") for name in top)


def test_recorded_trace():
    from chip import trace_reduce as tr

    red = tr.reduce(SMALL, 1)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["kernel_s"]["quantize"] > 0
    assert red["kernel_s"]["quantize"] < red["busy_s"]
    assert red["collective_s"] == 0
    assert len(red["breakdown"]["device_ops"]) == 10
