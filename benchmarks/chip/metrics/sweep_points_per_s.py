"""sweep_points_per_s: lattice points of the sweeps completed in the window,
over the time from the window's opening to the last completion in it.
Sweeps still in flight when the window closes are left out."""


def read(run):
    done = run.completed_in_window()
    if not done:
        return None
    last = max(r.done for r in done)
    return sum(r.points for r in done) / (last - run.t0)
