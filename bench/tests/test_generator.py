import numpy as np

import generator


def test_frames_are_due_evenly_at_the_rate():
    due = generator.frame_times(100.0, 16, 30.0)
    assert len(due) == 188           # 6.25 frames/s over 30 s, from t = 0
    assert due[0] == 0.0 and due[-1] < 30.0
    assert np.allclose(np.diff(due), 0.16)


def test_payloads_depend_on_the_seed_and_the_schedule_does_not():
    a = generator.frame_payload(2 ** 31 + 12345, 7, 16, 256)
    b = generator.frame_payload(2 ** 31 + 12345, 7, 16, 256)
    c = generator.frame_payload(2 ** 31 + 12346, 7, 16, 256)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["payload"], c["payload"])
    assert a["payload"].shape == (16, 256) and a["payload"].dtype == np.uint8
    assert a["payload"].min() >= 32 and a["payload"].max() < 127
    assert a["value"].dtype == np.float32


def test_draws_are_separate_streams_of_one_seed():
    x = generator.draws(99, "fractions").random(4)
    y = generator.draws(99, "fractions").random(4)
    z = generator.draws(99, "pools").random(4)
    assert np.array_equal(x, y) and not np.array_equal(x, z)
