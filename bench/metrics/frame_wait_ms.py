"""95th percentile of how long a frame waited from its due time until the
loop started it: queueing behind earlier frames, and generator lateness."""

import stats


def read(run):
    return stats.percentile([(i.start - i.due) * 1e3
                             for i in run.items("frame")], 95)
