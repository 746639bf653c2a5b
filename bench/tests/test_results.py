"""Percentiles over every request due in the window, tokens/s over the
window, and the per-layer readers' arithmetic on a hand-made run."""
import types

import pytest

from harness import driver, results, spec


def run_data(**kw):
    steps = driver.Steps()
    for t, n in [(1.0, 5), (2.0, 7), (3.0, 11), (4.0, 13)]:
        steps.t.append(t)
        steps.tokens.append(n)
        steps.prefills.append([(0, 10)] if t == 2.0 else [])
        steps.decodes.append([20] * n)
    recs = []
    for i, (due, first, last, n) in enumerate([
            (0.5, 1.0, 3.0, 5), (0.9, 2.0, 4.0, 9), (1.5, 3.0, 3.0, 1),
            (3.5, None, None, 0)]):
        recs.append(driver.Record(rid=f"{i}.0", due=due, first=first,
                                  last=last, n=n, counted=True))
    base = dict(records=recs, steps=steps, t_open=1.5, t_close=3.5,
                t_last=5.0, setup_s=12.0, counters_open={"x": 1},
                counters_close={"x": 4, "peak_pages_held": 12},
                pool_pages=48, chunk_steps=8, closed=False, config={},
                model=None, peak_flops=1e12, kind_of=lambda n: None)
    base.update(kw)
    return results.RunData(**base)


def test_ttft_counts_every_request_due_in_the_window():
    run = run_data()
    # The unserved request waits until the end of the drain (5.0 - 3.5).
    assert sorted(run.ttfts()) == pytest.approx([0.5, 1.1, 1.5, 1.5])
    assert spec.metric_reader("ttft_p50_s")(run) == pytest.approx(1.3)
    assert spec.metric_reader("ttft_p90_s")(run) == pytest.approx(1.5)


def test_tpot_over_requests_with_two_tokens_or_more():
    run = run_data()
    assert sorted(run.tpots()) == pytest.approx([0.25, 0.5])
    assert spec.metric_reader("tpot_p90_ms")(run) == pytest.approx(475.0)


def test_output_rate_is_window_tokens_over_window_length():
    run = run_data()
    # Steps returning in (1.5, 3.5]: t = 2.0 and 3.0 -> 18 tokens / 2 s.
    assert spec.metric_reader("output_tok_s")(run) == pytest.approx(9.0)


def test_device_readers_need_a_trace():
    run = run_data()
    for name in ("prefill_ms_per_wave", "prefill_mfu", "decode_step_ms.open",
                 "decode_mfu.batch", "device_idle_share.open"):
        assert spec.metric_reader(name)(run) is None


def test_device_readers_from_a_trace_summary():
    from harness.trace import TraceSummary

    model = types.SimpleNamespace(prefill_flops=lambda c, a, n: 1e9 * n,
                                  decode_flops=lambda c, ctx: 1e9)
    summary = TraceSummary(
        window_s=2.0, busy_s=1.5,
        programs={"jit_p": (2, 0.4), "jit_d": (3, 0.6)},
        top_ops=[], gaps=[], planes=["/device:TPU:0"])
    kinds = {"jit_p": "prefill", "jit_d": "decode"}
    run = run_data(trace=summary, trace_t=(1.5, 3.5), model=model,
                   kind_of=kinds.get)
    assert spec.metric_reader("prefill_ms_per_wave")(run) == pytest.approx(200)
    # Traced steps t=2.0 (10 prefilled tokens) and t=3.0.
    assert spec.metric_reader("prefill_mfu")(run) == pytest.approx(
        100 * 10e9 / (0.4 * 1e12))
    assert spec.metric_reader("decode_step_ms.open")(run) == pytest.approx(
        1000 * 0.6 / 24)
    assert spec.metric_reader("decode_mfu.open")(run) == pytest.approx(
        100 * 18e9 / (0.6 * 1e12))
    assert spec.metric_reader("device_idle_share.batch")(run) == \
        pytest.approx(25.0)


def test_counter_readers():
    run = run_data(counters_open={"prefix_tokens_shared": 10,
                                  "prefill_work_tokens": 5},
                   counters_close={"prefix_tokens_shared": 40,
                                   "prefill_work_tokens": 15,
                                   "peak_pages_held": 12})
    assert spec.metric_reader("prefix_hit_share")(run) == pytest.approx(75.0)
    assert spec.metric_reader("kv_pool_peak_share")(run) == pytest.approx(25.0)
