"""Tokens are counted where they are emitted: a completion falling
either side of the window's edge moves the count by that step's tokens,
never by a request."""
import pytest

from benchmark import stamps
from benchmark.stats import percentile


class FakeEngine:
    """Slots that each emit one token a step; a request of ``max_new``
    tokens finishes in the step that emits its last one."""

    def __init__(self):
        self.generated = {}
        self.budget = {}

    def submit(self, rid, max_new):
        self.generated[rid], self.budget[rid] = 0, max_new

    def step(self):
        for rid in self.generated:
            if self.generated[rid] < self.budget[rid]:
                self.generated[rid] += 1


def _run(step_s, n_steps, answers):
    """Four requests decoding together from t=0; returns the stamper's records."""
    eng = FakeEngine()
    st = stamps.TokenStamper(lambda rid: eng.generated[rid])
    for rid, n in enumerate(answers):
        eng.submit(rid, n)
        st.offer(rid, due=0.0, prompt_len=8, max_new=n)
    for i in range(1, n_steps + 1):
        eng.step()
        st.after_step(i * step_s)
    return st


def test_completion_either_side_of_the_edge_moves_the_count_by_a_step_not_a_request():
    # request 0 (40 tokens) finishes in step 40, at t = 40 * step_s
    answers = [40, 64, 64, 64]
    inside = _run(0.2499, 64, answers)   # its last token lands at 9.996 s: inside a 10 s window
    outside = _run(0.2501, 64, answers)  # ... at 10.004 s: just outside
    a = stamps.window_metrics(inside.requests, 0.0, 10.0)["tokens"]
    b = stamps.window_metrics(outside.requests, 0.0, 10.0)["tokens"]
    # counting at completion would credit request 0's 40 tokens to one run
    # and none to the other; stamps differ by the one step cut by the edge
    assert a - b == 4 and a == 4 * 40
    by_completion = [sum(len(r["stamps"]) for r in st.requests if r["done"] and r["stamps"][-1] < 10.0)
                     for st in (inside, outside)]
    assert by_completion[0] - by_completion[1] == 40


def test_tokens_of_unfinished_requests_count_and_requests_in_flight_are_not_failures():
    st = _run(0.25, 20, [64, 64, 64, 64])  # nobody finishes in the window
    w = stamps.window_metrics(st.requests, 1.0, 5.0)
    assert w["tokens"] == 4 * 16 and w["failed"] == 0 and st.live == 4
    assert w["attempted"] == 0  # all four were due before the window opened


def test_first_token_times_gaps_and_failures():
    eng = FakeEngine()
    st = stamps.TokenStamper(lambda rid: eng.generated.get(rid, 0))
    st.offer(None, due=1.0, prompt_len=8, max_new=4, refused=True)
    eng.submit(1, 3)
    st.offer(1, due=1.0, prompt_len=8, max_new=3)
    st.offer(2, due=2.0, prompt_len=8, max_new=3)   # never admitted: no first token by the close
    st.offer(3, due=9.8, prompt_len=8, max_new=3)   # due in the last tenth: may fairly still wait
    out = None
    for t in (1.5, 2.0, 2.75):
        eng.step()
        out = st.after_step(t)
    assert [r["id"] for r in out["finished"]] == [1] and st.live == 2
    w = stamps.window_metrics(st.requests, 0.0, 10.0)
    assert w["attempted"] == 4 and w["failed"] == 2  # the refused one, and request 2
    assert w["ttft_ms"] == [500.0] and w["gaps_ms"] == [500.0, 750.0]
    assert w["tpot_ms"] == [625.0] and w["oldest_waiting_s"] == 8.0


def test_first_token_comes_from_prefill_later_ones_from_decode():
    eng = FakeEngine()
    st = stamps.TokenStamper(lambda rid: eng.generated[rid])
    eng.submit(0, 5)
    st.offer(0, due=0.0, prompt_len=100, max_new=5)
    eng.step()
    assert st.after_step(1.0)["decode_fills"] == []      # the first token: the last prefill chunk's
    eng.step()
    assert st.after_step(2.0)["decode_fills"] == [101]   # 100 prompt rows + the first token's
    eng.generated[0] = 4                                  # two tokens in one look
    assert st.after_step(3.0)["decode_fills"] == [103]


def test_request_the_engine_retires_short_is_failed():
    eng = FakeEngine()
    st = stamps.TokenStamper(lambda rid: eng.generated[rid])
    eng.submit(0, 10)
    st.offer(0, due=0.5, prompt_len=8, max_new=10)
    eng.step()
    out = st.after_step(1.0, ended={0: False})
    assert out["finished"][0]["errored"] and st.live == 0
    assert stamps.window_metrics(st.requests, 0.0, 2.0)["failed"] == 1


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (95, 3.85), (100, 4.0)])
def test_percentile_interpolates_like_numpy(q, want):
    assert percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
