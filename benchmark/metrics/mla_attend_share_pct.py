"""The share of the traced prefill chunks' device time spent in **expanded latent attention**:
self time of the device operations under the named scope ``mla.attend`` (keys and values rebuilt a
head from the cached latents of the slot's context, block by block under an online softmax) inside
``jit_serve_prefill`` executions over their summed device time.  None where the trace holds no
such scope or program."""
from benchmark import scopes


def read(record):
    raw = scopes.of_run(record)
    return scopes.scope_share_pct(raw, "mla.attend", "jit_serve_prefill") if raw else None
