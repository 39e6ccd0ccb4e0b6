"""Share of the equal-heads paged-attention kernel's grid steps that hold
a chunk of some row's context, over the window: the program's counters
`paged_chunks_walked` (a decode step, the sum over its rows of the chunks
their contexts fill) over `paged_grid_steps` (the grid length of that
step's program).  The rest are a padding row's chunk or steps past the
list's end, which fetch nothing."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("paged_grid_steps") or "paged_chunks_walked" not in c:
        return None
    return 100.0 * c["paged_chunks_walked"] / c["paged_grid_steps"]
