"""95th percentile over every frame of the window that completed, of the
time from the frame's due time at the open-loop generator to its sink
outputs being ready on the device.  Frames that did not complete count
in ``failed``."""

import stats


def read(run):
    lat = [(i.end - i.due) * 1e3 for i in run.items("frame") if i.ok]
    return stats.percentile(lat, 95)
