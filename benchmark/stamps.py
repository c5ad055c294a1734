"""Token stamps: every output token gets the host time of the end of
the engine step that emitted it.  Rates, first-token times and gaps
are all taken from these stamps, so no end-to-end number depends on
which requests *finished* inside the window (the fault that made PR 22's
long-prompt cell jump by a request's worth of tokens).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

from .stats import percentile


class TokenStamper:
    """Follows requests through an engine it does not own.

    ``emitted(request_id)`` returns how many tokens the request has
    generated so far (``len(srv.result(id).generated)`` on the real
    engine).  Call :meth:`offer` when a request is submitted and
    :meth:`after_step` after every engine step with that step's end
    time; new tokens since the last look are stamped with it.
    """

    def __init__(self, emitted: Callable[[int], int]):
        self._emitted = emitted
        self._live: Dict[int, Dict[str, Any]] = {}
        self.requests: List[Dict[str, Any]] = []

    def offer(self, request_id: Optional[int], due: float, prompt_len: int, max_new: int,
              refused: bool = False) -> Dict[str, Any]:
        rec = {"id": request_id, "due": due, "prompt_len": prompt_len, "max_new": max_new,
               "stamps": [], "refused": refused, "done": False, "errored": False}
        self.requests.append(rec)
        if not refused:
            self._live[request_id] = rec
        return rec

    def after_step(self, now: float, ended: Optional[Dict[int, bool]] = None) -> Dict[str, Any]:
        """Stamp the tokens this step emitted.  ``ended`` maps the ids
        the engine retired in this step to whether they ended well; a
        request also ends when it reaches its answer length.  Returns
        ``{"finished": [records], "decode_fills": [cache rows each decoded
        request attended over]}`` — a request's first token comes out of
        its last prefill chunk, every later one out of a decode step."""
        ended = ended or {}
        finished, fills = [], []
        for rid, rec in list(self._live.items()):
            before = len(rec["stamps"])
            new = self._emitted(rid) - before
            rec["stamps"].extend([now] * new)
            if new - (1 if before == 0 and new > 0 else 0) > 0:
                fills.append(rec["prompt_len"] + len(rec["stamps"]) - 1)
            if len(rec["stamps"]) >= rec["max_new"] or rid in ended:
                rec["done"] = True
                rec["errored"] = not ended.get(rid, True) or len(rec["stamps"]) < rec["max_new"]
                finished.append(rec)
                del self._live[rid]
        return {"finished": finished, "decode_fills": fills}

    @property
    def live(self) -> int:
        return len(self._live)


def window_metrics(requests: List[Dict[str, Any]], t_open: float, t_close: float,
                   ttft_share: float = 0.9, open_loop: bool = False) -> Dict[str, Any]:
    """What the stamps say about ``[t_open, t_close)``.

    * ``tokens_emitted``: stamps inside the window, whichever request
      they belong to and whether or not it finished;
    * ``tokens``: what the rate counts.  In a closed loop the backlog is
      the load and the reading is capacity: every emitted token.  In an
      open loop (``open_loop``) only the stamps of requests **due inside
      the window** (the set ``attempted`` counts): the offered traffic's
      tokens served in time.  The backlog carried in from before the
      window leaves the number, so it is at most the offered rate and
      falls only as the engine falls behind — counting every stamp read
      *lower* the faster the engine drained what it carried in (PR 26);
    * ``ttft_ms``: due time → first stamp, over requests due in the
      first ``ttft_share`` of the window (later ones may fairly still be
      waiting at the close); one of those with no first token by the
      close is ``failed``, as is any refused request;
    * ``gaps_ms``: every gap between a request's consecutive stamps
      whose later stamp lies in the window;
    * ``attempted``: requests due inside the window.
    """
    tokens = emitted = 0
    ttft, gaps, tpot = [], [], []
    attempted = failed = 0
    oldest_wait = 0.0
    sample_end = t_open + ttft_share * (t_close - t_open)
    for r in requests:
        stamps = [s for s in r["stamps"] if s < t_close]
        inside = [s for s in stamps if s >= t_open]
        emitted += len(inside)
        if not open_loop or t_open <= r["due"] < t_close:
            tokens += len(inside)
        for a, b in zip(stamps, stamps[1:]):
            if b >= t_open:
                gaps.append((b - a) * 1e3)
        if len(inside) >= 2:
            tpot.append((inside[-1] - inside[0]) * 1e3 / (len(inside) - 1))
        if r["due"] < t_close and not r["refused"] and (not stamps or stamps[0] >= t_open):
            # waited for its first token during (part of) the window
            oldest_wait = max(oldest_wait, (stamps[0] if stamps else t_close) - r["due"])
        if t_open <= r["due"] < t_close:
            attempted += 1
            if r["refused"] or r["errored"]:
                failed += 1
            elif r["due"] < sample_end:
                if stamps:
                    ttft.append((stamps[0] - r["due"]) * 1e3)
                else:
                    failed += 1
    return {"tokens": tokens, "tokens_emitted": emitted, "window_s": t_close - t_open, "ttft_ms": ttft, "gaps_ms": gaps,
            "tpot_ms": tpot, "attempted": attempted, "failed": failed, "oldest_waiting_s": oldest_wait}


def pct(values: List[float], q: float) -> Optional[float]:
    return percentile(values, q) if values else None
