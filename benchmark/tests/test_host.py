"""Tests of the readers of what the host was doing while the chip idled
(``readers/host.py``), on device and host events written by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.readers import host, trace  # noqa: E402

MS = 1e6  # ns
DEV = "/device:TPU:0"
ENGINE, OTHER = "/host:CPU#0:python", "/host:CPU#1:python"


def ops(*pairs):
    """Device operations ``[start, stop)`` in milliseconds."""
    return [(f"%fusion.{i}", a * MS, (b - a) * MS)
            for i, (a, b) in enumerate(pairs)]


def span(name, a, b, line=ENGINE):
    return (line, name, a * MS, (b - a) * MS)


#: a window of 100 ms with ops at 10-20, 30-50 and 60-90: idle 0-10 and
#: 90-100 (the edges), 20-30 and 50-60 (the gaps) — 40 ms, 40%
DEVICES = {DEV: ops((10, 20), (30, 50), (60, 90))}
SPANS = [
    span("engine/admit", 5, 15),        # host over idle 5-10
    span("engine/fetch_wait", 18, 32),  # a wait over the whole 20-30 gap
    span("engine/resolve", 45, 58),     # host over 55-58 ...
    span("engine/resolve_wait", 50, 55),  # ... nested, a wait over 50-55
    span("host/gc2", 52, 54, OTHER),    # a collection off the engine line
    span("engine/record", 92, 95),      # host in the last edge
]


def ctx_of(devices=DEVICES, spans=SPANS, window_s=0.1, **kw):
    ctx = {"devices": devices, "host_spans": spans, "trace_span": [0.0, 0.1],
           "device_busy": trace.device_busy(devices, window_s)}
    return dict(ctx, **kw)


def test_idle_is_charged_to_host_or_unseen_and_adds_up():
    ctx = ctx_of()
    a = host.idle_attribution(ctx)
    # host: admit 5 + gc over a wait 2 + resolve 3 + record 3
    assert a["host_ns"] == pytest.approx(13 * MS)
    assert a["unseen_ns"] == pytest.approx(27 * MS)
    assert host.device_idle_host_share(ctx) == pytest.approx(13.0)
    assert host.device_idle_unseen_share(ctx) == pytest.approx(27.0)
    assert (host.device_idle_host_share(ctx)
            + host.device_idle_unseen_share(ctx)) == pytest.approx(
                trace.idle_share(ctx), abs=1e-9)
    by = host.idle_by_host(ctx)
    assert by == pytest.approx({
        "gc": 0.002, "admit": 0.005, "fetch_wait": 0.010,
        "resolve_wait": 0.003, "resolve": 0.003, "record": 0.003,
        "none": 0.014})


@pytest.mark.parametrize("window_s, devices", [
    (0.1, DEVICES),
    (0.25, DEVICES),                            # a window past the ops
    (0.08, {DEV: ops((50, 90), (95, 130))}),  # ops past the window's end
    (0.1, {DEV: ops((-5, 10), (40, 60))}),      # ops before the profile
])
def test_host_and_unseen_make_the_idle_share(window_s, devices):
    ctx = ctx_of(devices, window_s=max(
        window_s, trace.span_ns(devices) / 1e9))
    share = trace.idle_share(ctx)
    both = (host.device_idle_host_share(ctx)
            + host.device_idle_unseen_share(ctx))
    assert both == pytest.approx(share, abs=1e-9)
    assert host.device_idle_host_share(ctx) >= 0
    assert host.device_idle_unseen_share(ctx) >= 0


def test_nested_phases_charge_the_innermost():
    line = [span("engine/consume", 0, 10), span("engine/stream", 2, 4),
            span("engine/record", 4, 5), span("engine/gc", 6, 7)]
    got = [(n, a / MS, b / MS) for n, a, b in host.innermost(line)]
    assert got == [("consume", 0, 2), ("stream", 2, 4), ("record", 4, 5),
                   ("consume", 5, 6), ("gc", 6, 7), ("consume", 7, 10)]
    # a wait nested in host work, and host work nested in a wait
    spans = [span("engine/resolve", 0, 10), span("engine/resolve_wait", 2, 8),
             span("engine/fetch_wait", 20, 30), span("engine/record", 24, 25)]
    assert [(a / MS, b / MS) for a, b in host.host_busy(spans)] == [
        (0, 2), (8, 10), (24, 25)]


def test_a_collection_on_any_line_is_host_work():
    alone = [span("engine/fetch_wait", 0, 100),
             span("host/gc0", 22, 26, OTHER)]
    ctx = ctx_of(spans=alone)
    assert host.idle_attribution(ctx)["host_ns"] == pytest.approx(4 * MS)
    assert host.gc_pause_ms_per_s(ctx) == pytest.approx(4 / 0.1)
    assert host.gc_by_phase(ctx) == {"-": pytest.approx(0.004)}
    # on the engine's own line it is charged to the phase it interrupted
    ctx = ctx_of(spans=[span("engine/fetch_wait", 0, 100),
                        span("host/gc2", 22, 26)])
    assert host.gc_by_phase(ctx) == {"fetch_wait": pytest.approx(0.004)}


def test_no_device_plane_or_no_engine_line_reads_none():
    readers = (host.device_idle_host_share, host.device_idle_unseen_share,
               host.gc_pause_ms_per_s, host.idle_attribution,
               host.idle_by_host, host.clock_check)
    no_device = {"devices": {}, "host_spans": SPANS, "trace_span": [0, 1]}
    no_engine = ctx_of(spans=[span("host/gc2", 22, 26, OTHER)])
    not_read = ctx_of(spans=None)  # a runner that keeps no host spans
    for ctx in (no_device, no_engine, not_read):
        for reader in readers:
            assert reader(ctx) is None, (reader.__name__, ctx)
    assert host.gc_by_phase(no_engine) is None
    # a program that has the engine's phases and no collection spans
    assert host.gc_pause_ms_per_s(ctx_of(spans=SPANS[:1])) is None


def test_clock_check_counts_gaps_that_end_before_their_launch():
    ctx = ctx_of(spans=SPANS + [span("engine/dispatch", 28, 29)])
    got = host.clock_check(ctx)
    # gaps 20-30 (latest launch 28) and 50-60 (28): lags 2 and 32 ms
    assert got == {"gaps": 2, "violations": 0, "before_capture": 0,
                   "median_lag_ms": pytest.approx(17.0)}
    late = [(line, name, a + 40 * MS, d) for line, name, a, d in SPANS]
    got = host.clock_check(ctx_of(spans=late))
    # admit now starts at 45: the gap ending at 30 has no launch before it,
    # and no engine event either
    assert got["violations"] == 1 and got["gaps"] == 2
    assert got["before_capture"] == 1
    assert got["median_lag_ms"] == pytest.approx(15.0)
    # a gap with the engine in the capture and no launch yet is not
    # excused: the host's clock is then behind the device's
    seen = late + [span("engine/fetch_wait", 25, 35)]
    got = host.clock_check(ctx_of(spans=seen))
    assert got["violations"] == 1 and got["before_capture"] == 0
    # gaps of 1 ms or less are not held to it
    short = ctx_of(devices={DEV: ops((10, 20), (20.5, 30))})
    assert host.clock_check(short)["gaps"] == 0
    # a park launches too (the slot update and its operands)
    parked = late + [span("engine/park", 26, 31)]
    got = host.clock_check(ctx_of(spans=parked))
    assert got["violations"] == 0 and got["median_lag_ms"] == pytest.approx(
        (4 + 15) / 2)


def test_engine_phase_share_reads_the_wave_records():
    def wave(ts, wave_s, host_s):
        return {"kind": "wave", "ts": ts, "wave_s": wave_s, "host_s": host_s}

    ctx = {"window": [100.0, 200.0], "flight_records": [
        wave(101, None, {"record": 9.0}),  # a run's first: no interval
        wave(102, 0.1, {"record": 0.01, "stream": 0.002, "fetch_wait": 0.08}),
        wave(103, 0.1, {"record": 0.03, "fetch_wait": 0.07}),
        wave(300, 0.1, {"record": 0.1}),  # outside the window
    ]}
    assert host.engine_phase_share(ctx, phase="record") == pytest.approx(20)
    assert host.engine_phase_share(ctx, phase="stream") == pytest.approx(1)
    old = {"window": [100.0, 200.0], "flight_records": [
        wave(102, 0.1, {"consume": 0.01, "fetch_wait": 0.08})]}
    assert host.engine_phase_share(old, phase="record") is None


def test_every_new_metric_file_matches_its_benchmark_entry():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, phase in (("engine_record_share.decode", "record"),
                        ("engine_stream_share.decode", "stream")):
        spec = json.load(open(os.path.join(
            ROOT, "benchmark", "metrics", name + ".json")))
        assert spec["reader"] == "host"
        assert spec["args"] == {"phase": phase}
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == declared[name][key], (name, key)
        assert getattr(host, spec["function"])(
            {"window": [0.0, 1.0], "flight_records": []},
            **spec["args"]) is None


def test_host_spans_on_a_recorded_xplane(tmp_path):
    """The program's own spans: a clock's phases and a collection charged
    to it, on one line of the host plane."""
    import gc

    import jax

    from tpustack.obs import flight

    clock = flight.PhaseClock()
    flight.gc_attach(clock)
    try:
        jax.profiler.start_trace(str(tmp_path))
        with clock.phase("admit"):
            gc.collect()
        jax.profiler.stop_trace()
    finally:
        flight.gc_detach()
    spans = host.host_spans(trace.find_xplane(str(tmp_path)))
    names = {name for _, name, _, _ in spans}
    assert {"engine/admit", "host/gc2"} <= names
    assert len(host.engine_lines(spans)) == 1
    (line, _, a, d), = [s for s in spans if s[1] == "engine/admit"]
    gcs = [s for s in spans if s[1] == "host/gc2" and s[0] == line]
    assert any(a <= s[2] and s[2] + s[3] <= a + d for s in gcs)
