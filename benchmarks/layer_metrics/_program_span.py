"""What the readers of the program's own stage spans share: the median
duration, in milliseconds, of one span name over the timed window
(``program_span_s``: durations by name, which the fused loop's runner
collects from ``spans_player0.jsonl``). None where the program writes no
such span, as a checkout from before the span does."""

from statistics import median


def median_ms(ctx, span: str):
    durations = ctx.values.get("program_span_s", {}).get(span)
    return 1e3 * median(durations) if durations else None
