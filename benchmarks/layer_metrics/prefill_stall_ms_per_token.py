"""Row-ms that seated rows spent behind another request's prompt, a decoded
token: 1e3 x `prefill_row_stall_s` (the sum over prefills of their
duration times the rows seated when they were called) over
`decode_tokens`, the whole window. What chunked prefill is to remove."""
from step_trace import per


def read(run):
    return per(run, "prefill_row_stall_s", "decode_tokens", 1e3)
