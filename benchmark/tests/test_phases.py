"""Tests of the readers of the engine's timeline (``readers/phases.py``), on
records written by hand.  Run by hand or in a rehearsal, as the other tests
of the benchmark's own yardstick:

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

from benchmark.readers import phases  # noqa: E402

CFG = {"hidden_size": 3584, "num_attention_heads": 28,
       "num_key_value_heads": 4, "intermediate_size": 18944,
       "vocab_size": 152064, "num_hidden_layers": 28}
W = [100.0, 200.0]


def prefill(ts, queue_s, admit_s, prefill_s, lens=None, **kw):
    rec = {"kind": "prefill", "ts": ts, "rows": len(queue_s),
           "queue_s": queue_s, "admit_s": admit_s, "prefill_s": prefill_s}
    if lens is not None:
        rec["prompt_lens"] = lens
    return dict(rec, **kw)


def wave(ts, wave_s, host_s, tokens=8, passes=16, kind="wave"):
    rec = {"kind": kind, "ts": ts, "wave_s": wave_s, "tokens": tokens,
           "weight_passes": passes}
    if host_s is not None:
        rec["host_s"] = host_s
    return rec


def ctx_of(records, **kw):
    return dict({"window": W, "flight_records": records, "cfg": CFG}, **kw)


# -------------------------------------------------------------- the splits
def test_slot_wait_and_admit_to_first_are_exact_percentiles_of_rows():
    # 11 rows in the window, queue_s 0.00 .. 0.10: the 90th percentile by
    # linear interpolation between closest ranks is the 10th value, 0.09
    recs = [prefill(110 + i, [i / 100.0], [0.001], 0.5 + i / 100.0)
            for i in range(11)]
    # a group of two rows shares its prefill_s; both rows count
    recs.append(prefill(150, [0.0, 0.0], [0.0, 0.0], 0.0))
    ctx = ctx_of(recs)
    q = sorted([i / 100.0 for i in range(11)] + [0.0, 0.0])
    want = q[10] + (q[11] - q[10]) * (0.9 * 12 - 10)
    assert phases.rows_quantile_ms(ctx, fields=["queue_s"], q=90) == \
        pytest.approx(want * 1e3)
    a = sorted([0.001 + 0.5 + i / 100.0 for i in range(11)] + [0.0, 0.0])
    want = a[10] + (a[11] - a[10]) * (0.9 * 12 - 10)
    assert phases.rows_quantile_ms(
        ctx, fields=["admit_s", "prefill_s"], q=90) == pytest.approx(
            want * 1e3)


def test_window_edges_and_rows_with_a_part_missing():
    recs = [prefill(99.999, [9.0], [9.0], 9.0),      # before the window
            prefill(100.0, [0.2], [0.0], 0.1),       # on its first edge: in
            prefill(200.0, [0.4], [0.0], 0.3),       # on its last edge: in
            prefill(200.001, [9.0], [9.0], 9.0),     # after it
            prefill(150.0, [None], [0.0], 0.1),      # no enqueue time
            {"kind": "wave", "ts": 150.0, "queue_s": [7.0]}]  # another kind
    ctx = ctx_of(recs)
    assert sorted(phases.row_seconds(ctx, "queue_s")) == [0.2, 0.4]
    # the row without queue_s still has its admit -> first
    assert sorted(phases.row_seconds(ctx, "admit_s", "prefill_s")) == \
        pytest.approx([0.1, 0.1, 0.3])
    assert phases.rows_quantile_ms(ctx, fields=["queue_s"], q=50) == \
        pytest.approx(300.0)


def test_engine_host_share_leaves_the_waits_and_the_first_record_out():
    recs = [
        wave(101, None, {"admit": 5.0, "fetch_wait": 1.0}),  # a run's first
        wave(102, 0.30, {"admit": 0.003, "dispatch": 0.002, "consume": 0.004,
                         "fetch_wait": 0.28, "resolve_wait": 0.01,
                         "other": 0.001}),
        wave(103, 0.10, {"verify": 0.002, "verify_wait": 0.09,
                         "draft": 0.006, "consume": 0.002}, kind="verify"),
        wave(250, 0.30, {"admit": 0.3}),                     # after the window
    ]
    share = phases.engine_host_share(ctx_of(recs))
    assert share == pytest.approx(100.0 * (0.010 + 0.010) / 0.40)
    assert 0.0 <= share <= 100.0


def test_tokens_per_weight_pass_counts_waves_and_verifies():
    recs = [wave(101, None, None, tokens=7, passes=16),
            wave(102, 0.3, None, tokens=8, passes=16),
            wave(103, 0.1, None, tokens=5, passes=1, kind="verify"),
            wave(300, 0.3, None, tokens=100, passes=1)]
    assert phases.tokens_per_weight_pass(ctx_of(recs)) == \
        pytest.approx(20 / 33)


def test_flash_prefill_roofline_charges_each_call_its_own_shape():
    h, hd, S = 28, 128, 4096
    one = 4.0 * hd * h * S * (S + 1) / 2          # a row at the bucket
    assert phases.causal_call_flops(
        "%flash_panel.3 = bf16[28,4096,128]{2,1,0:T(8,128)(2,1)} "
        "custom-call(bf16[28,4096,128]{2,1,0} %p0)") == pytest.approx(one)
    assert phases.causal_call_flops("%flash_panel.3") is None
    # 28 calls of a one-row group at 5 ms, 28 of a two-row group at 10 ms,
    # under both names; a fusion and the wrapper's old name are not counted
    dev = {"/device:TPU:0": [
        (f"%flash_panel.{i} = bf16[28,4096,128]{{2,1,0}} custom-call(...)",
         i * 1e7, 5e6) for i in range(28)] + [
        (f"%flash_kstream.{i} = bf16[56,4096,128]{{2,1,0}} custom-call(...)",
         1e9 + i * 2e7, 1e7) for i in range(28)] + [
        ("%fusion.3 = bf16[56,4096,128]{2,1,0} fusion(...)", 5e9, 1e6),
        ("%_flash_attention.1 = bf16[56,4096,128]{2,1,0} custom-call(...)",
         6e9, 1e6)]}
    peaks = {"flops_bf16": 197e12}
    got = phases.flash_prefill_roofline(ctx_of([], devices=dev, peaks=peaks))
    assert got == pytest.approx(
        100.0 * (28 * 3 * one / 197e12) / (28 * 5e-3 + 28 * 1e-2))
    assert 0.0 < got < 100.0
    # a run that happens to trace only two-row groups reads the same share
    # as one that traces only one-row groups: nothing is paired by count
    two = {"/device:TPU:0": dev["/device:TPU:0"][28:56]}
    assert phases.flash_prefill_roofline(
        ctx_of([], devices=two, peaks=peaks)) == pytest.approx(got)
    # a call whose name carries no shape is not charged a guess
    dev["/device:TPU:0"].append(("%flash_panel.99", 7e9, 1e6))
    assert phases.flash_prefill_roofline(
        ctx_of([], devices=dev, peaks=peaks)) is None


def test_paged_ctx_roofline_reads_the_waves_of_the_traced_span():
    # int8 KV: 2 * 4 kv heads * (128 + 4) bytes a token a layer
    cfg = dict(CFG, kv="int8")
    per_layer_tok = 2 * 4 * (128 + 4)
    recs = [dict(wave(149, 0.3, None, passes=16), ctx_tokens=9000),  # before
            dict(wave(151, 0.3, None, passes=16), ctx_tokens=1000),
            dict(wave(152, 0.3, None, passes=16), ctx_tokens=2000),
            dict(wave(153, 0.1, None, passes=1, kind="verify"),
                 ctx_tokens=3000),
            dict(wave(155, 0.3, None, passes=16), ctx_tokens=9000)]  # after
    dev = {"/device:TPU:0": [
        (f"%paged_attention.{i} = (f32[8,4,16,128]) custom-call(...)",
         i * 1e6, 2e5) for i in range(100)]}
    peaks = {"hbm_bytes_per_s": 819e9}
    ctx = ctx_of(recs, cfg=cfg, devices=dev, peaks=peaks,
                 trace_span=[150.0, 154.0])
    mean_ctx = (16 * 1000 + 16 * 2000 + 1 * 3000) / 33
    least = 100 * mean_ctx * per_layer_tok / 819e9
    assert phases.paged_ctx_roofline(ctx) == pytest.approx(
        100.0 * least / (100 * 2e-4))
    # no wave in the span, or none that counted its context: nothing to read
    assert phases.paged_ctx_roofline(dict(ctx, trace_span=[10, 20])) is None
    assert phases.paged_ctx_roofline(dict(ctx, flight_records=[
        wave(151, 0.3, None)])) is None


def test_prefill_padding_share_counts_positions_not_asked_for():
    recs = [prefill(110, [0.0, 0.0], [0.0, 0.0], 1.0, lens=[2049, 3584],
                    bucket=4096, cached_tokens=0),
            # a prefix hit: 3000 tokens, 2488 of them cached, the suffix of
            # 512 computed at a bucket of 512
            prefill(120, [0.0], [0.0], 0.1, lens=[3000], bucket=512,
                    cached_tokens=2488),
            prefill(300, [0.0], [0.0], 1.0, lens=[1], bucket=4096)]  # outside
    got = phases.prefill_padding_share(ctx_of(recs))
    assert got == pytest.approx(
        100.0 * (1 - (2049 + 3584 + 512) / (2 * 4096 + 512)))
    # a group with no bucket on its record (the parent's) is left out
    assert phases.prefill_padding_share(ctx_of(
        [prefill(110, [0.0], [0.0], 1.0, lens=[5])])) is None


# ------------------------------------------ nothing to read is None, never 0
def test_an_empty_window_reads_none():
    empty = ctx_of([])
    assert phases.rows_quantile_ms(empty, fields=["queue_s"]) is None
    assert phases.rows_quantile_ms(empty,
                                   fields=["admit_s", "prefill_s"]) is None
    assert phases.engine_host_share(empty) is None
    assert phases.tokens_per_weight_pass(empty) is None
    assert phases.flash_prefill_roofline(empty) is None
    assert phases.flash_prefill_roofline(
        ctx_of([], devices={}, peaks={"flops_bf16": 1.0})) is None
    assert phases.prefill_padding_share(empty) is None
    assert phases.paged_ctx_roofline(empty) is None
    assert phases.rows_quantile_ms({"window": W}) is None  # no records key


def test_a_program_without_the_fields_reads_none_and_does_not_raise():
    # what the parent commit writes: prefill records with a scalar
    # prefill_s only, wave records with no host_s
    recs = [{"kind": "prefill", "ts": 150.0, "rows": 2,
             "prompt_tokens": 700, "cached_tokens": 0, "prefill_s": 0.4},
            wave(151, 0.3, None)]
    dev = {"/device:TPU:0": [
        ("%_flash_attention.39 = bf16[56,4096,128] custom-call()", 0.0, 5e6),
        ("%paged_attention.7 = (f32[8,4,16,128]) custom-call()", 1e7, 2e5)]}
    ctx = ctx_of(recs, devices=dev, trace_span=[100.0, 200.0],
                 peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    assert phases.rows_quantile_ms(ctx, fields=["queue_s"]) is None
    assert phases.rows_quantile_ms(ctx,
                                   fields=["admit_s", "prefill_s"]) is None
    assert phases.engine_host_share(ctx) is None
    assert phases.flash_prefill_roofline(ctx) is None
    assert phases.paged_ctx_roofline(ctx) is None
    assert phases.prefill_padding_share(ctx) is None
    # the counters were there before the reader was
    assert phases.tokens_per_weight_pass(ctx) == pytest.approx(0.5)


def test_every_new_metric_file_names_a_reader_that_exists():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    new = ["slot_wait_p90_ms", "admit_to_first_p90_ms",
           "engine_host_share.decode", "engine_host_share.prefill",
           "tokens_per_weight_pass", "flash_prefill_roofline",
           "paged_attention_ctx_roofline", "prefill_padding_share"]
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in new:
        spec = json.load(open(os.path.join(
            ROOT, "benchmark", "metrics", name + ".json")))
        assert spec["reader"] == "phases"
        assert callable(getattr(phases, spec["function"]))
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == declared[name][key], (name, key)
        # the reader runs on an empty window with the file's own arguments
        assert getattr(phases, spec["function"])(
            {"window": W, "flight_records": [], "cfg": CFG},
            **spec["args"]) is None
