"""Positions the decoding rows' selections kept over the positions they could attend, summed by the engine's
host over the window's decode steps (``ServingEngine.stats()``: ``dsa_positions_selected`` /
``dsa_positions_attendable``).  None from an engine that reports no such counters."""


def read(record):
    s = record["counters"].get("engine_stats") or {}
    if not s.get("dsa_positions_attendable"):
        return None
    return 100.0 * s["dsa_positions_selected"] / s["dsa_positions_attendable"]
