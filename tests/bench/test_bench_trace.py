"""The trace reduction on a small trace recorded on the chip: SSB Q4.1 at
65,536 lineorder rows, two runs inside the ``bench.window`` annotation, on
one TPU v5 lite (``bench/record_trace.py``, gzipped)."""
from __future__ import annotations

import gzip
import warnings

import numpy as np
import pytest

from bench_helpers import ROOT
from bench import events
from bench.trace import clip, covered, op_name, reduce_xplane, union

TRACE = str(ROOT / "bench/testdata/q4.1_65k.xplane.pb.gz")


@pytest.fixture(scope="module")
def summary():
    return reduce_xplane(TRACE, devices=1)


def _raw_ops(window):
    """The device's XLA op intervals, read without the reduction."""
    from jax.profiler import ProfileData
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        with gzip.open(TRACE, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
        plane = next(p for p in data.planes if p.name == "/device:TPU:0")
        line = next(ln for ln in plane.lines if ln.name == events.OPS_LINE)
        return [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                if e.start_ns + e.duration_ns > window[0]
                and e.start_ns < window[1]]


def test_window_is_the_harness_annotation(summary):
    assert summary.window == (45135772.0, 459767268.0)
    assert summary.window_ns == 414631496.0


def test_busy_time_is_the_union_of_device_ops(summary):
    ops = _raw_ops(summary.window)
    # an independent sweep: mark every covered nanosecond boundary
    edges = np.array(sorted({t for iv in ops for t in iv}
                            | set(summary.window)))
    mid = (edges[:-1] + edges[1:]) / 2
    hit = np.zeros(len(mid), dtype=bool)
    for a, b in ops:
        hit |= (mid > a) & (mid < b)
    inside = (mid > summary.window[0]) & (mid < summary.window[1])
    expect = float(np.sum(np.diff(edges)[hit & inside]))
    assert summary.busy_ns == [pytest.approx(expect)]
    assert summary.busy_ns == [166135020.0]
    idle = 100 * (1 - summary.mean_busy_ns / summary.window_ns)
    assert idle == pytest.approx(59.93, abs=0.01)


def test_device_time_by_program_and_op(summary):
    # the fused segment program holds nearly all of the busy time
    assert summary.program_ns("segment") == 165915387.0
    assert summary.program_ns("groupby") == 10845.0
    top = max(summary.ops_ns, key=summary.ops_ns.get)
    assert top == "jit__kernel/%while.12"
    assert summary.ops_ns[top] == 48473520.0
    assert all("/" in name for name in summary.ops_ns)


def test_idle_gaps_are_named_by_what_the_host_did(summary):
    names = [n for n, _ in summary.gaps]
    lengths = [t for _, t in summary.gaps]
    assert len(summary.gaps) == 10
    assert lengths == sorted(lengths, reverse=True)
    assert summary.gaps[0] == ("bench.run > np.asarray(jax.Array)",
                               27115418.0)
    assert all(n.startswith(("bench.", "outside")) for n in names)
    assert sum(lengths) <= summary.window_ns - summary.busy_ns[0]


def test_interval_helpers():
    assert union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert clip([(0, 5), (6, 9), (10, 12)], (4, 11)) == [(4, 5), (6, 9),
                                                         (10, 11)]
    assert covered([(0, 2), (1, 3), (5, 6)]) == 4
    assert op_name("%while.3 = (s32[]) while(...)", "jit__kernel(123)") \
        == "jit__kernel/%while.3"
    assert op_name("%copy.1 = u8[4] copy(...)", None) == "?/%copy.1"


def test_two_devices_need_two_planes():
    with pytest.raises(ValueError):
        reduce_xplane(TRACE, devices=2)
