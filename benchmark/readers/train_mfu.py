"""Model FLOP/s utilisation, in percent: 6 N tokens/s over the chips'
bf16 peak, at the median of the sync-to-sync readings (the step as the
model runs it; a stall belongs to ``train_tok_s``). Attention's products
and recomputed work are not counted."""

import statistics

from benchmark import estimators, peaks


def read(obs, args, ctx):
    if ctx.platform != "tpu":
        return None  # a share of a chip's peak is a device metric
    readings = estimators.sync_readings(obs["syncs"], obs["tokens_per_sync"])
    if not readings:
        return None
    return peaks.mfu(statistics.median(readings), obs["model"],
                     obs["device"]["count"], obs["device"]["kind"])
