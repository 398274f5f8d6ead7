"""Mean context a busy slot-step attended in the measured window:
``ServeStats.positions_attended`` over ``slot_steps_busy``. A program
without the counter gives nothing to read."""


def read(run):
    stats = run.observed.stats_window
    busy = stats.get("slot_steps_busy")
    if "positions_attended" not in stats or not busy:
        return None
    return stats["positions_attended"] / busy
