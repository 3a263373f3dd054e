"""The least bytes one round has to move (each sampled row once per local
step) at the chip's peak bandwidth, over the traced round time. In percent.
A configuration that is not bandwidth-bound gives no bytes and reads
nothing."""


def read(run):
    return run.share_of_peak(run.min_bytes_per_round, "hbm_bytes_per_s")
