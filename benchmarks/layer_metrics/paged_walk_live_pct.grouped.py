"""Share of the grouped paged-attention kernels' grid steps that hold a
chunk of some row's context or window, over the window: the program's
counters `paged_chunks_walked` (a decode step, the sum over the work
lists its program walks — the full layers' pool, the sliding layers' —
and over its rows of the chunks their contexts or windows fill) over
`paged_grid_steps` (those lists' grid lengths in that step's program).
The rest are a padding row's chunk or steps past a list's end, which
fetch nothing.  A program that does not count these kernels (the parent
of PR 32) leaves both zero: nothing to read."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("paged_grid_steps") or "paged_chunks_walked" not in c:
        return None
    return 100.0 * c["paged_chunks_walked"] / c["paged_grid_steps"]
