"""device_idle_share.sweep: 1 - (union of device op intervals / traced
window), in percent, averaged over the chips used."""


def read(run):
    share = run.idle_share()
    return None if share is None else 100.0 * share
