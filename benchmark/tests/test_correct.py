"""What ``harness/correct.py`` compares, on made-up gaps: no model."""
import types

import numpy as np
import pytest

from harness import correct


def _gaps(n_wide, width, n=320, small=0.004):
    gaps = np.full(n, small)
    gaps[:n_wide] = width
    return gaps


LIMITS = {"wide_gap": 0.25, "wide_gap_share": 0.012, "capped_gap_mean": 0.008}


@pytest.mark.parametrize("n_wide, width, small, holds", [
    (0, 0.0, 0.004, True),      # rounding alone
    (1, 4.2, 0.004, True),      # one token given other experts: no fault
    (3, 1.4, 0.004, True),
    (4, 0.31, 0.004, False),    # many wide gaps: the share
    (0, 0.0, 0.009, False),     # every gap a little wider: the capped mean
    (46, 5.0, 0.0, False),      # a launch in seven altered: both
])
def test_wide_gaps_are_counted_not_weighed(n_wide, width, small, holds):
    read = correct._numbers(_gaps(n_wide, width, small=small),
                             LIMITS["wide_gap"])
    assert all(read[k] <= LIMITS[k] for k in
               ("wide_gap_share", "capped_gap_mean")) is holds
    # the widest gap and the plain mean are read all the same
    assert read["logit_gap_max"] == max(width, small)


def test_no_position_compared_is_not_correct():
    read = correct._numbers(np.empty(0), 0.25)
    assert read["logit_gap_max"] == float("inf")
    assert read["wide_gap_share"] == 1.0


def _track(index, n_prompt, n_out=4, done=True):
    req = types.SimpleNamespace(index=index, prompt=np.zeros(n_prompt),
                                n_out=n_out)
    handle = types.SimpleNamespace(truncated=False, output_ids=[1] * n_out)
    return types.SimpleNamespace(req=req, handle=handle, done=done,
                                 measured=True)


def test_sample_holds_the_longest_and_no_document_twice():
    """A closed loop cycles its pool: three rounds of eight documents."""
    tracks = [_track(i % 8, 100 + 10 * (i % 8)) for i in range(24)]
    tracks.append(_track(8, 900, done=False))
    picked = correct.pick_sample(tracks, 5, 6)
    assert picked[0].req.index == 7 and picked[0] is tracks[7]
    assert len({t.req.index for t in picked}) == len(picked) == 6
    assert [t.req.index for t in picked] == \
        [t.req.index for t in correct.pick_sample(tracks, 5, 6)]
    assert len(correct.pick_sample(tracks, 5, 20)) == 8
