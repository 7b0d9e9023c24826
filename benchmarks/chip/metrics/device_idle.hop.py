"""device_idle.hop: 1 - (union of the device's operation intervals) / the
traced window of whole LiGO hops, in percent."""


def read(run):
    return None if run.summary is None else 100.0 * run.summary.idle_share
