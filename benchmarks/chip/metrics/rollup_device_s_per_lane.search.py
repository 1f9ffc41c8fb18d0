"""rollup_device_s_per_lane.search: device seconds of the roll-up program
(``_eval_kernel``) in the trace, summed over chips, per engine lane."""

PROGRAM = "_eval_kernel"


def read(run):
    secs, lanes = run.module_s(PROGRAM), run.lanes()
    return secs / lanes if secs and lanes else None
