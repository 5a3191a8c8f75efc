"""Mean host time inside ``StreamExecutor.process_frame`` per frame: the
executor's dispatch, transfers, device work and service waits."""

import stats


def read(run):
    return stats.mean([(i.end - i.start) * 1e3 for i in run.items("frame")])
