"""prefill_useful_share.*: prompt tokens prefilled over the prefill rows
computed in the window, from the engine's counters at the window's edges;
left out where the engine does not count the rows."""
import pytest

from harness import driver, results, spec

NAMES = ("prefill_useful_share.open", "prefill_useful_share.batch")


def run_with(counters_open, counters_close):
    return results.RunData(
        records=[], steps=driver.Steps(), t_open=0.0, t_close=1.0,
        t_last=1.0, setup_s=0.0, counters_open=counters_open,
        counters_close=counters_close, pool_pages=1, chunk_steps=8,
        closed=False, config={}, model=None, peak_flops=1e12,
        kind_of=lambda n: None)


@pytest.mark.parametrize("name", NAMES)
def test_useful_rows_over_rows_computed(name):
    # Two waves of 24 slots at the 512 bucket admitting 300 prompt tokens.
    run = run_with({"prefill_work_tokens": 100, "prefill_rows_computed": 0},
                   {"prefill_work_tokens": 400,
                    "prefill_rows_computed": 2 * 24 * 512})
    assert spec.metric_reader(name)(run) == pytest.approx(
        100 * 300 / (2 * 24 * 512))


@pytest.mark.parametrize("name", NAMES)
def test_no_wave_in_the_window_reads_nothing(name):
    c = {"prefill_work_tokens": 10, "prefill_rows_computed": 64}
    assert spec.metric_reader(name)(run_with(c, dict(c))) is None


@pytest.mark.parametrize("name", NAMES)
def test_an_engine_without_the_counter_reads_nothing(name):
    c = {"prefill_work_tokens": 10}
    assert spec.metric_reader(name)(run_with(c, {"prefill_work_tokens": 90})) \
        is None
