"""The fullest held expert's tokens over the mean held expert's, in the
worst expert layer, over the window (``ServingEngine.stats()["moe"]``,
fed by the counts the serve programs return beside the tokens).  1.0 is
a perfectly even router; the deployment's slowest chip waits for its
fullest expert.  None from an engine that reports no expert counters."""


def read(record):
    moe = record["counters"].get("moe")
    return moe.get("load_max_over_mean") if moe else None
