"""extract_s_per_lane.sweep: ``engine.extract`` seconds (Pareto masks,
slice records, materialised points, cache writes) per engine lane of the
window."""


def read(run):
    return run.per_lane("engine.extract")
