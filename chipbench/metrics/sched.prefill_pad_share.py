"""Per cent of the prefill rows x columns dispatched over the window that
were AOT bucket padding (the program's ``bucket_pad_tokens`` against the
real prefill and continuation tokens)."""


def read(rec):
    c = rec.counters
    pad = c.get("bucket_pad_tokens", 0)
    real = c.get("prefill_tokens", 0) + c.get("continuation_prefill_tokens", 0)
    return 100.0 * pad / (pad + real) if pad + real else None
