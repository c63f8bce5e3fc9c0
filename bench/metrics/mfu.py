"""Model FLOPs of the window's work (bench/flops.py: real prompt tokens
prefilled, tokens decoded, the visible attention pairs, the head once per
sampled position) over the window's seconds times the chip's bf16 peak
(bench/peaks.json), in %: the whole step's share of the peak."""


def read(rec):
    peak = rec.peak.get("bf16_flops")
    if not peak or not rec.ticks:
        return None
    return 100.0 * sum(t.flops(rec.dm) for t in rec.ticks) / (rec.window_s * peak)
