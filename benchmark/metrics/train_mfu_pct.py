"""Model FLOP/s utilisation: tokens/s/chip x (6 N + 12 L D seq) over the
chip's published bf16 peak; recomputed operations are not counted."""
from benchmark.stats import peak


def read(record):
    w, sh = record["window"], record["shapes"]
    elapsed = w["t_close"] - w["t_open"]
    per_chip = w["steps"] * w["tokens_per_step"] / elapsed / sh["n_devices"]
    return 100.0 * per_chip * sh["flops_per_token"] / peak(record["device"]["kind"])["bf16_flops"]
