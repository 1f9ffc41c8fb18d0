"""execute_s_per_lane.sweep: ``engine.execute`` seconds (roll-up dispatch,
device-to-host copy, numpy tail) per engine lane of the window."""


def read(run):
    return run.per_lane("engine.execute")
