"""The scheduler's own host work a decode step: eviction, admission
(without the prefill call), page-table growth and batch assembly,
token bookkeeping and retirement (`evict_s` + `admit_host_s` +
`decode_prep_s` + `book_s`) over `occupancy_steps`."""
from program_trace import counter_ratio_ms


def read(run):
    return counter_ratio_ms(
        run, ["evict_s", "admit_host_s", "decode_prep_s", "book_s"],
        "occupancy_steps")
