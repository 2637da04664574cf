"""The traffic generator drives any mix its data file states."""
import jax
import jax.numpy as jnp
import pytest

from bench import loop

OPEN = {"loop": "open", "arrivals": "poisson", "rate_per_s": 40, "burst": 2,
        "pool": 2, "keep": 3, "warmup": 0}
CLOSED = {"loop": "closed", "clients": 3, "pool": 2, "keep": 3, "warmup": 0}


@jax.jit
def _work(i):
    return {"i": i, "y": jnp.cumsum(jnp.ones(4096) * i)}


def _call(i):
    return _work(jnp.int32(i))


def _drive(mix, seconds=0.25, seed=5):
    return loop.drive(mix, _call, seconds, seed=seed,
                      summarize=lambda out: int(out["i"]))


def test_poisson_arrivals_same_gaps_other_order():
    a = loop.arrival_times(OPEN, 2.0, seed=1)
    b = loop.arrival_times(OPEN, 2.0, seed=2)
    assert len(a) == len(b) == 80 * 2
    gaps = [sorted(y - x for x, y in zip([0.0] + t[::2], t[::2]))
            for t in (a, b)]
    assert gaps[0] == pytest.approx(gaps[1])
    assert a != b
    assert a == loop.arrival_times(OPEN, 2.0, seed=1)
    assert a[0] == a[1] > 0                 # bursts of two


def test_uniform_arrivals_are_evenly_spaced():
    t = loop.arrival_times(dict(OPEN, arrivals="uniform", burst=1), 1.0, 9)
    assert t == pytest.approx([(k + 1) / 40 for k in range(40)])


def test_open_loop_answers_every_arrival_in_order():
    win = _drive(OPEN)
    assert win.calls == len(loop.arrival_times(OPEN, 0.25, 5))
    assert win.summaries == list(range(win.calls))
    assert len(win.kept) == 3 and all(v > 0 for v in win.latency_s)
    for i, out in win.kept.items():
        assert int(out["i"]) == i


@pytest.mark.parametrize("clients", [1, 3])
def test_closed_loop_runs_past_the_window(clients):
    win = _drive(dict(CLOSED, clients=clients))
    assert win.summaries == list(range(win.calls))
    assert win.seconds >= 0.25 and win.calls >= clients


def test_end_to_end_by_name():
    win = loop.Window(4, 2.0, {}, [0] * 4, [0.1, 0.2, 0.3, 0.4])
    assert loop.end_to_end("items_per_s", win, 10) == 20.0
    assert loop.end_to_end("query_p95_ms", win, 10) == pytest.approx(400.0)
    assert loop.end_to_end("query_p50_ms", win, 10) == pytest.approx(200.0)
    with pytest.raises(KeyError):
        loop.end_to_end("ttft_ms", win, 10)


@pytest.mark.parametrize("bad", [{"loop": "closed", "clients": 0},
                                 {"loop": "open", "arrivals": "bursty",
                                  "rate_per_s": 5},
                                 {"loop": "open", "arrivals": "poisson",
                                  "rate_per_s": 0},
                                 {"loop": "replay"}])
def test_unknown_mix_is_refused(bad):
    with pytest.raises(ValueError):
        loop.check_mix(dict({"pool": 1, "keep": 1, "warmup": 0}, **bad))
