"""The traffic generator: seeded, fixed work per seed, unique rows."""
import numpy as np
import pytest

from perfbench import run, traffic

CATALOG = run.load_catalog()
SEED = 3_000_000_017          # larger than 32 signed bits hold


@pytest.mark.parametrize("name", ["batch", "single", "repeat"])
def test_same_seed_same_schedule_and_rows(name):
    mix = traffic.load_mix(name)

    def draw(seed):
        d = traffic.Drawer(mix, CATALOG, seed)
        return d.rows(500, traffic.rng_for(seed, 1, 3))

    r1, r2, r3 = draw(SEED), draw(SEED), draw(SEED + 1)
    np.testing.assert_array_equal(r1.X, r2.X)
    assert not np.array_equal(r1.X, r3.X)


def test_closed_sizes_are_fixed_and_spread_in_any_short_run():
    """Every client cycles through the whole grid, and each aligned run of
    8 sizes holds one size from each eighth of the range."""
    mix = traffic.load_mix("batch")
    grid = np.sort(traffic.size_grid(mix))
    for c in range(mix["clients"]):
        sizes = traffic.closed_sizes(mix, c)
        np.testing.assert_array_equal(np.sort(sizes), grid)
        for k in range(0, len(sizes), 8):
            run = np.sort(sizes[k:k + 8])
            assert all(grid[8 * j] <= v <= grid[8 * j + 7]
                       for j, v in enumerate(run))


def test_open_schedule_is_the_same_gaps_reordered():
    mix = traffic.load_mix("single")
    a = traffic.open_schedule(mix, SEED, 4.0)
    b = traffic.open_schedule(mix, SEED, 4.0)
    c = traffic.open_schedule(mix, SEED + 1, 4.0)
    np.testing.assert_array_equal(a, b)
    assert len(a) == round(mix["rate_per_s"] * 4.0)
    assert a[-1] == pytest.approx(4.0) and c[-1] == pytest.approx(4.0)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(c, prepend=0)))


@pytest.mark.parametrize("name", ["batch", "single"])
def test_fresh_rows_never_repeat(name):
    mix = traffic.load_mix(name)
    rows = traffic.Drawer(mix, CATALOG, SEED).rows(
        20000, traffic.rng_for(SEED, 1, 3))
    assert rows.fresh.all()
    assert len(np.unique(rows.X, axis=0)) == len(rows.X)


def test_repeat_draws_catalog_rows_zipf_with_a_few_fresh():
    """The generator's Zipf draw, which a mix may name, over the repeat
    mix's arrivals."""
    mix = dict(traffic.load_mix("repeat"), draw="zipf", zipf_s=1.1,
               fresh=0.02)
    rows = traffic.Drawer(mix, CATALOG, SEED).rows(
        20000, traffic.rng_for(SEED, 1, 3))
    assert rows.fresh.sum() == round(mix["fresh"] * 20000)
    np.testing.assert_array_equal(rows.X[~rows.fresh],
                                  CATALOG[rows.idx[~rows.fresh]])
    counts = np.sort(np.bincount(rows.idx, minlength=len(CATALOG)))[::-1]
    # Zipf 1.1: the most drawn row is drawn far more than the median one
    assert counts[0] > 20 * max(counts[len(counts) // 2], 1)


def test_repeat_sends_every_catalog_row_as_it_is():
    """Every kernel is one priced before: no fresh row, no row favoured,
    and in a window's worth of draws every catalog row comes up."""
    mix = traffic.load_mix("repeat")
    rows = traffic.Drawer(mix, CATALOG, SEED).rows(
        20000, traffic.rng_for(SEED, 1, 3))
    assert not rows.fresh.any()
    np.testing.assert_array_equal(rows.X, CATALOG[rows.idx])
    counts = np.bincount(rows.idx, minlength=len(CATALOG))
    assert counts.min() > 0 and counts.max() < 3 * counts.mean()


def test_dispatch_sizes_cover_every_merge():
    assert traffic.dispatch_sizes(traffic.load_mix("batch"), 64) == [
        32, 64, 128, 256, 512, 1024, 2048, 4096]
    assert traffic.dispatch_sizes(traffic.load_mix("single"), 64) == [
        1, 2, 4, 8, 16, 32, 64]
    queues = {"loop": "open", "clients": 8, "rows": {"min": 16, "max": 256}}
    assert traffic.dispatch_sizes(queues, 1) == [16, 32, 64, 128, 256]


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        traffic.rng_for(-1, 0)
