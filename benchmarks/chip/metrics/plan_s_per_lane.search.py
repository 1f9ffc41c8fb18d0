"""plan_s_per_lane.search: ``engine.plan`` seconds (host ``SpecTables``)
per engine lane of the window."""


def read(run):
    return run.per_lane("engine.plan")
