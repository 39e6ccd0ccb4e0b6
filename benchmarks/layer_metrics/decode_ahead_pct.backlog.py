"""Share of the window's decode steps the scheduler launched before it
had read the step before them: 100 x the program's counter
`decode_steps_ahead` over `occupancy_steps`.  The rest followed a read
(an admission, a bucket that could shrink, the first step of a batch).
None where the program has no such counter."""


def read(run):
    c = run.get("counters") or {}
    if "decode_steps_ahead" not in c or not c.get("occupancy_steps"):
        return None
    return 100.0 * c["decode_steps_ahead"] / c["occupancy_steps"]
