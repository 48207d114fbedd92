"""p50/p90 over every request from its scheduled time; completions over
the window."""
import pytest

import stats


class R:
    def __init__(self, due_s, host_s, gave_up_s=None):
        self.due_s, self.host_s, self.gave_up_s = due_s, host_s, gave_up_s


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 5.5), (90, 9.1),
                                    (100, 10.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile(range(1, 11), q) == pytest.approx(want)


def test_latency_is_timed_from_the_scheduled_send():
    # sent late or queued, the clock starts at the scheduled time
    recs = [R(due_s=i * 0.1, host_s=i * 0.1 + 0.2) for i in range(10)]
    e = stats.end_to_end(recs, 10.0)
    assert e["p50_ms"] == pytest.approx(200.0)
    assert e["p90_ms"] == pytest.approx(200.0)


def test_tail_counts_every_request_even_unanswered():
    recs = [R(due_s=0.0, host_s=0.1) for _ in range(9)]
    recs.append(R(due_s=1.0, host_s=None, gave_up_s=61.0))
    e = stats.end_to_end(recs, 10.0)
    assert e["p50_ms"] == pytest.approx(100.0)
    # the unanswered request, timed to when the run gave up, is the tail
    assert e["p90_ms"] == pytest.approx(100.0 + 0.1 * (60000.0 - 100.0))


def test_completed_qps_counts_completions_inside_the_window():
    recs = [R(due_s=0.0, host_s=t) for t in (1.0, 2.0, 9.9, 10.0, 10.5)]
    recs.append(R(due_s=9.0, host_s=None, gave_up_s=70.0))
    assert stats.end_to_end(recs, 10.0)["completed_qps"] == \
        pytest.approx(4 / 10.0)
