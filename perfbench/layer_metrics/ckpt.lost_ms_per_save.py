"""Milliseconds of the window that plain steps do not explain, per staged
save: window seconds less (steps x the median step with no save due) and
less what the traced run spent opening and closing the profiler, over the
saves.  The checkpoint engine's own pause counter sees only the staging
call; a step that stands still while the writer thread copies the snapshot
to the host shows here (and in ``train_tokens_per_s``), not there."""

LAYER = "checkpoint"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    import statistics

    s = run["samples"]
    saves = run["counters"].get("ckpt.saves_staged_total", 0)
    plain = [t for t, due in zip(s["step_s"], s["step_had_save"]) if not due]
    if not saves or not plain:
        return None
    lost = (run["window_s"] - run.get("profiler_s", 0.0)
            - len(s["step_s"]) * statistics.median(plain))
    return lost / saves * 1e3
