"""The knee read from a sweep: the highest rate where 90% of requests met
both limits and the queue did not grow."""
import sys

import pytest

from tinytree import BENCH

sys.path.insert(0, str(BENCH / "tools"))
from knee import knee  # noqa: E402


def _line(rate, met, first, last):
    return {"rate_rps": rate, "met_share": met,
            "queue_wait_first_third_s": first, "queue_wait_last_third_s": last}


@pytest.mark.parametrize("lines, want", [
    ([_line(2, 1.0, 0.01, 0.01), _line(4, 0.95, 0.02, 0.03),
      _line(6, 0.8, 0.1, 0.5)], 4),
    # the queue grew at 6 although 90% met the limits
    ([_line(2, 1.0, 0.01, 0.01), _line(6, 0.92, 0.05, 0.4)], 2),
    # nothing met the limits: no knee, the sweep has to reach lower
    ([_line(3, 0.5, 0.1, 0.1), _line(5, 0.2, 0.1, 0.9)], None),
])
def test_knee(lines, want):
    assert knee(lines) == want
