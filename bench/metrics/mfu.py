"""Model FLOP/s utilization of the whole train step: model FLOPs per token
(bench/flops.py, PaLM App. B, no recomputation) times the traced run's
tokens per second, over the chips' bf16 peak (bench/peaks.json)."""
READS = {"host": "tokens_per_s"}


def read(run):
    if not run.tokens_per_s:
        return None
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * run.flops_per_token * run.tokens_per_s / peak
