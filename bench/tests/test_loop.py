"""The end-to-end metrics a window yields, on a window built by hand."""
import pytest

from bench import loop


def stat(i, due, first=None, win=None):
    r = loop.RequestStat(i, 100, due, first_token=first)
    if win:
        r.win_first, r.win_last = win
    return r


def window():
    reqs = [stat(0, 9.0, 9.5, ((10.0, 3), (12.0, 7))),   # due before the open
            stat(1, 10.0, 10.2, ((10.2, 1), (11.2, 11))),
            stat(2, 11.0, 11.4, ((11.4, 1), (11.4, 1))),  # one token only
            stat(3, 19.0),                                # still waiting
            stat(4, 19.5, 20.7)]                          # first token late
    return loop.Window(10.0, 20.0, reqs)


def test_ttft_counts_every_request_due_in_the_window():
    got = loop.ttft_ms(window())
    # request 0 was due before the open; 3 and 4 have no first token by the
    # close and count as close - due
    assert got == pytest.approx([200.0, 400.0, 1000.0, 500.0])


def test_tpot_and_tokens():
    w = window()
    assert loop.tpot_ms(w) == pytest.approx([2000.0 / 4, 1000.0 / 10])
    assert loop.tokens_in_window(w) == 5 + 11 + 1
