"""What the readers of the stream-delivery layer share: sums over the
window's spans of one name, one a stream: `decode.stream.read`, recorded by
the thread that read the stream (`DecodeStream.tokens()`: what a ready token
waited for its reader to run, `wake_s`; what the consumer did with it,
`consume_s`; the reader thread's CPU, `cpu_s`; `tokens`), and
`http.generate` (`write_s` over `chunks`). A program without the span, or
without the field, gives None."""
from benchmark.metrics._program import window_spans

READ = "decode.stream.read"


def fields_with(run, span, field):
    """The fields of the window's spans called `span` that carry `field`."""
    return [s["fields"] for s in window_spans(run, span) or ()
            if s["fields"].get(field) is not None]


def ms_per(run, span, field, per):
    """1000 x the sum of `field` (seconds) / the sum of the count `per`
    over the window's spans called `span`."""
    rows = fields_with(run, span, field)
    n = sum(f.get(per) or 0 for f in rows)
    return 1000.0 * sum(f[field] for f in rows) / n if n else None
