"""Mean over the requests that retired in the window of the largest gap
between two consecutive tokens of one (`token_gap_max_s` over
`tpot_requests`), in ms (the steady cell: an answer takes a quarter second).
The gap is summed when the request retires, whenever it happened: a
request seated before the window opened brings the gaps of the ramp with
it (its neighbours' prefills as the batch filled), so where answers
outlast the ramp the number is the ramp's and not the window's. A
neighbour's prefill or a stalled step shows here and in no mean."""
from step_trace import per


def read(run):
    return per(run, "token_gap_max_s", "tpot_requests", 1e3)
