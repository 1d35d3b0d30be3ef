"""The traffic generator: deterministic from the seed, within its stated
bounds, and offering every seed the same sizes in another order."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

BENCH = Path(__file__).resolve().parents[1]
BIG_SEED = 2**33 + 12345  # the driver's seeds exceed 32 bits


@pytest.mark.parametrize("mix_name", ["chat", "docs"])
def test_same_seed_same_jobs(mix_name):
    mix = traffic.load_mix(BENCH, mix_name)
    a = traffic.generate(mix, 200, BIG_SEED, 50304, rate=5.0)
    b = traffic.generate(mix, 200, BIG_SEED, 50304, rate=5.0)
    assert [(j.due, j.max_new, j.klass) for j in a] == \
        [(j.due, j.max_new, j.klass) for j in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix_name", ["chat", "docs"])
def test_bounds_hold(mix_name):
    mix = traffic.load_mix(BENCH, mix_name)
    jobs = traffic.generate(mix, 512, 7, 1000, rate=5.0)
    p = np.array([len(j.prompt) for j in jobs])
    o = np.array([j.max_new for j in jobs])
    assert p.min() >= 1 and p.max() <= mix["prompt"]["max"]
    assert o.min() >= mix["output"]["min"] and o.max() <= mix["output"]["max"]
    assert (p + o).max() <= mix["max_total"]
    assert all(j.prompt.min() >= 0 and j.prompt.max() < 1000 for j in jobs)


def test_seeds_offer_the_same_sizes():
    mix = traffic.load_mix(BENCH, "chat")
    a = traffic.generate(mix, 150, 1, 100, rate=5.0)
    b = traffic.generate(mix, 150, 2, 100, rate=5.0)
    key = lambda jobs: sorted((len(j.prompt), j.max_new) for j in jobs)
    assert sorted(len(j.prompt) for j in a) == sorted(len(j.prompt) for j in b)
    assert sorted(j.max_new for j in a) == sorted(j.max_new for j in b)
    assert key(a) != [(len(j.prompt), j.max_new) for j in a]  # reordered
    gaps = lambda jobs: sorted(np.round(np.diff([j.due for j in jobs]), 9))
    # the same gaps but one (the first request is due at 0)
    assert len(set(gaps(a)) - set(gaps(b))) <= 1


def test_open_loop_schedule():
    mix = traffic.load_mix(BENCH, "chat")
    n = traffic.open_loop_count(6.0, 30.0)
    jobs = traffic.generate(mix, n, 3, 100, rate=6.0)
    dues = [j.due for j in jobs]
    assert n == 180 and dues[0] == 0.0
    assert all(b >= a for a, b in zip(dues, dues[1:]))
    assert dues[-1] < 30.0
    assert 25.0 < dues[-1]  # the rate is what the cell says


def test_closed_loop_blocks_repeat_sizes():
    mix = traffic.load_mix(BENCH, "docs")
    jobs = traffic.generate(mix, 128, 5, 100)
    assert all(j.due is None for j in jobs)
    first = sorted(j.max_new for j in jobs[:64])
    second = sorted(j.max_new for j in jobs[64:])
    assert first == second


def test_classes_keep_their_shares(tmp_path):
    mix = {"loop": "open", "max_total": 100,
           "prompt": {"dist": "fixed", "value": 8, "min": 1, "max": 50},
           "output": {"dist": "uniform", "min": 2, "max": 9},
           "classes": [{"name": "latency", "share": 0.3},
                       {"name": "bulk", "share": 0.7}]}
    jobs = traffic.generate(mix, 100, 9, 50, rate=2.0)
    assert sum(j.klass == "latency" for j in jobs) == 30
    assert all(len(j.prompt) == 8 for j in jobs)


def test_open_loop_needs_a_rate():
    mix = traffic.load_mix(BENCH, "chat")
    with pytest.raises(ValueError):
        traffic.generate(mix, 10, 1, 100)


def _write_mix(root, name, mix):
    (root / "traffic").mkdir(exist_ok=True)
    (root / "traffic" / f"{name}.json").write_text(json.dumps(mix))


@pytest.mark.parametrize("change", [
    {"loop": "sessions"},
    {"arrivals": "bursty"},
    {"arrivals": "poisson", "arrival_params": {"burst_x": 4}},
    {"shared_prefix": 512},
    {"loop": "closed", "arrivals": "poisson"},
], ids=["loop", "arrivals", "arrival_param", "key", "closed_arrivals"])
def test_what_the_generator_does_not_know_is_refused(tmp_path, change):
    """A mix that asks for a loop, an arrival kind, a parameter or a key
    the generator does not implement is refused, not run as Poisson."""
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix.update(change)
    _write_mix(tmp_path, "m", mix)
    with pytest.raises(ValueError):
        traffic.load_mix(tmp_path, "m")


def test_new_arrival_kind_is_a_file_alone(tmp_path, monkeypatch):
    """An arrival kind added as ``arrivals/<kind>.py`` is found by name and
    drives the schedule; the generator is not edited."""
    arrivals = tmp_path / "arrivals"
    arrivals.mkdir()
    (arrivals / "poisson.py").write_text(
        (BENCH / "arrivals" / "poisson.py").read_text())
    (arrivals / "even.py").write_text(
        "import numpy as np\n"
        "PARAMS = ('spacing',)\n"
        "def gaps(levels, rate, params):\n"
        "    return np.full(levels.shape, params['spacing'] / rate)\n")
    monkeypatch.setattr(traffic, "ARRIVALS", arrivals)
    mix = {**traffic.load_mix(BENCH, "chat"), "arrivals": "even",
           "arrival_params": {"spacing": 2.0}}
    _write_mix(tmp_path, "even-chat", mix)
    jobs = traffic.generate(traffic.load_mix(tmp_path, "even-chat"), 5,
                            BIG_SEED, 100, rate=4.0)
    assert [j.due for j in jobs] == [0.0, 0.5, 1.0, 1.5, 2.0]
