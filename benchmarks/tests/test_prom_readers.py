import pytest

from lib import client, prom
from lib.context import Context
from readers import (client_records, prometheus_delta, prometheus_sample,
                     setup, trace_ops, trace_roofline)

PAGE0 = '''# HELP x y
kubeinfer_prefix_cache_hits_total 2
kubeinfer_prefix_cache_misses_total 2
kubeinfer_engine_step_duration_seconds_sum{phase="decode"} 10.0
kubeinfer_engine_step_duration_seconds_sum{phase="prefill"} 1.0
kubeinfer_inference_completion_tokens_total 100
kubeinfer_engine_batch_occupancy 0.5
kubeinfer_device_peak_bytes_in_use{device="0"} 1000000000
kubeinfer_device_peak_bytes_in_use{device="1"} 3000000000
'''
PAGE1 = PAGE0.replace("hits_total 2", "hits_total 8") \
    .replace('decode"} 10.0', 'decode"} 16.0') \
    .replace("tokens_total 100", "tokens_total 140") \
    .replace("occupancy 0.5", "occupancy 0.7")


def _ctx(**kw):
    return Context(seconds=10.0, setup_s=99.0,
                   scrapes=[(0.0, PAGE0), (10.0, PAGE1)], **kw)


def test_prom_value_sums_matching_labels_and_tells_absent_from_zero():
    assert prom.value(PAGE0, "kubeinfer_prefix_cache_hits_total") == 2
    assert prom.value(PAGE0, "kubeinfer_device_peak_bytes_in_use") == 4e9
    assert prom.value(PAGE0, "kubeinfer_engine_step_duration_seconds_sum",
                      {"phase": "decode"}) == 10.0
    assert prom.value(PAGE0, "nothing_here") is None
    assert prom.by_label(PAGE0, "kubeinfer_device_peak_bytes_in_use",
                         "device") == {"0": 1e9, "1": 3e9}


def test_delta_is_taken_over_the_window():
    hit = prometheus_delta.read({
        "num": [{"series": "kubeinfer_prefix_cache_hits_total"}],
        "den": [{"series": "kubeinfer_prefix_cache_hits_total"},
                {"series": "kubeinfer_prefix_cache_misses_total"}]}, _ctx())
    assert hit == 1.0  # 6 hits, 0 misses inside the window
    ms = prometheus_delta.read({
        "scale": 1000.0,
        "num": [{"series": "kubeinfer_engine_step_duration_seconds_sum",
                 "labels": {"phase": "decode"}}],
        "den": [{"series": "kubeinfer_inference_completion_tokens_total"}]},
        _ctx())
    assert ms == pytest.approx(150.0)


def test_delta_reports_nothing_when_nothing_happened_or_is_absent():
    args = {"num": [{"series": "kubeinfer_prefix_cache_misses_total"}],
            "den": [{"series": "kubeinfer_prefix_cache_misses_total"}]}
    assert prometheus_delta.read(args, _ctx()) is None
    args = {"num": [{"series": "absent"}], "den": [{"series": "absent"}]}
    assert prometheus_delta.read(args, _ctx()) is None


def test_gauges_are_sampled_not_differenced():
    occ = prometheus_sample.read(
        {"series": "kubeinfer_engine_batch_occupancy", "agg": "mean"},
        _ctx())
    assert occ == pytest.approx(0.6)
    peak = prometheus_sample.read(
        {"series": "kubeinfer_device_peak_bytes_in_use", "agg": "max",
         "over": "max", "scale": 1e-9}, _ctx())
    assert peak == pytest.approx(3.0)
    assert prometheus_sample.read(
        {"series": "absent", "agg": "mean"}, _ctx()) is None


def _records():
    out = []
    for i, (ttft, n) in enumerate([(100, 11), (200, 21), (300, 1),
                                   (400, 11), (500, 11)]):
        out.append(client.Record(
            index=i, due_s=i, send_s=i, done_s=i + 2.0, status=200,
            max_tokens=n, tokens=[0] * n, route="continuous",
            server_ttft_ms=ttft, server_tpot_ms=100.0))
    return out


def test_client_records_stats():
    recs = _records()
    ctx = _ctx(window=recs)
    p50 = client_records.read({"field": "ttft_ms", "stat": "p50"}, ctx)
    assert p50 == pytest.approx(300.0)
    tok = client_records.read({"field": "n_tokens", "stat": "sum_per_s"},
                              ctx)
    assert tok == pytest.approx(sum(len(r.tokens) for r in recs) / 10.0)
    # a one-token reply has no decode span: left out of the gap statistic
    assert client_records.read({"field": "tpot_ms", "stat": "mean"},
                               ctx) == pytest.approx(100.0)
    assert client_records.read({"field": "ttft_ms", "stat": "p50"},
                               _ctx()) is None
    assert setup.read({}, ctx) == 99.0


def test_trace_readers_report_nothing_without_a_trace():
    assert trace_ops.read({"stat": "idle_share"}, _ctx()) is None
    assert trace_roofline.read({"match": "x", "opcount": "quant_matmul"},
                               _ctx()) is None


MFU0 = '''kubeinfer_engine_prefill_tokens_total{kind="computed"} 1000
kubeinfer_engine_prefill_tokens_total{kind="cached"} 5000
kubeinfer_inference_completion_tokens_total 200
kubeinfer_inference_requests_total{route="continuous",outcome="ok"} 10
kubeinfer_engine_step_duration_seconds_sum{phase="decode"} 10.0
kubeinfer_engine_step_duration_seconds_sum{phase="prefill"} 1.0
'''
MFU1 = MFU0.replace('computed"} 1000', 'computed"} 4000') \
    .replace("tokens_total 200", "tokens_total 1200") \
    .replace('"ok"} 10', '"ok"} 30') \
    .replace('decode"} 10.0', 'decode"} 17.0') \
    .replace('prefill"} 1.0', 'prefill"} 2.0')


def test_step_mfu_prices_the_computed_tokens_over_the_steps_own_time():
    from lib import cell as cells
    from readers import model_flops

    conf = cells.load_json("configs", "qwen2-7b-w8.json")
    args = dict(cells.load_json("layer_metrics", "step.mfu.json")["reader"])
    args.pop("kind")
    ctx = Context(seconds=51.0, setup_s=1.0, config=conf,
                  scrapes=[(0.0, MFU0), (51.0, MFU1)],
                  peaks={"bf16_flops": 197e12})
    price = cells.sizes("qwen2").flops_per_token(conf)
    ops = price["layers"] * (3000 + 1000 - 20) + price["head"] * 1000
    assert model_flops.read(args, ctx) == pytest.approx(
        100 * ops / (8.0 * 197e12))
    # cached tokens are not computed: they move nothing
    more = MFU1.replace('cached"} 5000', 'cached"} 9000')
    ctx.scrapes = [(0.0, MFU0), (51.0, more)]
    assert model_flops.read(args, ctx) == pytest.approx(
        100 * ops / (8.0 * 197e12))
    # nothing computed, no peaks or a missing counter: nothing, never 0
    ctx.scrapes = [(0.0, MFU0), (51.0, MFU0)]
    assert model_flops.read(args, ctx) is None
    ctx.scrapes = [(0.0, MFU0), (51.0, MFU1)]
    ctx.peaks = None
    assert model_flops.read(args, ctx) is None
    ctx.peaks = {"bf16_flops": 197e12}
    ctx.scrapes = [(0.0, MFU0), (51.0, "nothing 1\n")]
    assert model_flops.read(args, ctx) is None
