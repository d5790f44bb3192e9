"""peak_hbm_gib: an upper bound of the most device memory the run held
on its fullest chip, over 2^30: `device.memory_stats()` read by the driver
at the window's close, when only the program under test has run in the
process (startup, the step programs, the feeds; the plain reference runs
after the reading, PR 30, so no step is too small to be seen):
`peak_bytes_in_use` (live arrays: parameters, optimizer state, feeds, a
step's outputs from its dispatch on) plus `peak_bytes_reserved` (the
running program's scratch, which this runtime books apart and peaks
apart). A training loop reaches the sum, because every step holds its
outputs and its scratch together (run.py says how that was checked). What
a batch has to fit into the chip's 16 GB. A backend that reports neither
gives no number (never `memory_analysis()` under this name)."""


def compute(run):
    peak = run["memory_peak_bytes"]
    return None if not peak else peak / 2.0**30
