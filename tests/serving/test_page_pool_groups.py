"""Layer groups in ``PagePool`` (docs/DESIGN.md §20): a model with
sliding-window layers keeps a second group of pages, of which a sequence
holds only what its next ``window`` keys can lie in."""

import numpy as np
import pytest

from zookeeper_tpu.serving.decode.pages import (
    PagePool,
    allocate_page_pool,
    page_pool_bytes,
)

PS, WINDOW, MAX_PAGES = 4, 8, 20


def grouped(**kw):
    args = dict(
        num_pages=64, page_size=PS, slots=3, max_pages_per_slot=MAX_PAGES,
        prefix_cache=False, window=WINDOW, window_pages=3 * 4,
    )
    args.update(kw)
    return PagePool(**args)


def test_one_group_behaves_as_before():
    """No window: no second group, the operand is the table itself, and
    the release is a no-op."""
    pool = PagePool(
        num_pages=32, page_size=PS, slots=2, max_pages_per_slot=8,
        prefix_cache=True,
    )
    assert pool.window_group is None
    assert pool.assign_prompt(0, np.arange(10)) == {"shared_tokens": 0, "cow": None}
    assert pool.operand().shape == (2, 8)
    np.testing.assert_array_equal(pool.operand(), pool.table)
    assert pool.operand([0], 2).shape == (2, 8)
    np.testing.assert_array_equal(pool.operand([0], 2)[1], -1)
    assert pool.release_behind_window([10, 0]) == 0
    assert "window_num_pages" not in pool.status()
    pool.release_slot(0)
    assert pool.leak_check() == 0


def test_admission_allocates_the_tail_of_the_window_group():
    pool = grouped()
    assert pool.assign_prompt(0, np.arange(30)) is not None
    group = pool.window_group
    # the full group: all 8 pages of 30 tokens
    assert int(pool.counts[0]) == 8 and np.all(pool.table[0, :8] >= 0)
    # the next query sits at 30 and reaches keys 23..30: pages 5, 6, 7
    assert group.first_needed(30) == 5
    assert list(np.flatnonzero(group.table[0] >= 0)) == [5, 6, 7]
    assert pool.operand().shape == (2, 3, MAX_PAGES)
    np.testing.assert_array_equal(pool.operand()[1], group.table)


@pytest.mark.parametrize("prompt", [1, 7, 8, 9, 30])
def test_release_behind_the_window_once_an_iteration(prompt):
    """Decode 40 tokens: each iteration grows both groups by what the
    next row needs, then frees what the window left behind. The window
    group never holds more than the window's pages and one; nothing
    leaks."""
    pool = grouped()
    assert pool.assign_prompt(1, np.arange(prompt)) is not None
    group = pool.window_group
    lengths = [0, prompt, 0]
    freed = 0
    for _ in range(40):
        assert pool.ensure_rows(1, lengths[1] + 1)
        lengths[1] += 1
        freed += pool.release_behind_window(lengths)
        live = np.flatnonzero(group.table[1] >= 0)
        # every key the next query can reach is held...
        lo = max(lengths[1] - WINDOW + 1, 0) // PS
        assert live[0] == lo
        # ...through the row written last, and no page more
        assert live[-1] == (lengths[1] - 1) // PS
        assert len(live) <= WINDOW // PS + 1
        assert pool.leak_check() == 0
    assert freed == group.released_behind > 0
    assert pool.status()["window_released_behind"] == freed
    pool.release_slot(1)
    assert pool.leak_check() == 0
    assert pool.used_pages == 0
    assert len(group._free) == group.num_pages


def test_both_groups_or_neither():
    """The window group out of pages refuses the admission and the growth
    before the full group allocates anything."""
    pool = grouped(window_pages=3)
    assert pool.assign_prompt(0, np.arange(9)) is not None  # pages 0..2
    used = pool.used_pages
    assert pool.assign_prompt(1, np.arange(4)) is None
    assert pool.used_pages == used and int(pool.counts[1]) == 0
    assert pool.exhausted_events == 1
    assert not pool.ensure_rows(0, 13)  # a fourth window page: none free
    assert pool.used_pages == used
    # the release makes room: at length 12 page 0 lies behind keys 5..12
    assert pool.release_behind_window([12, 0, 0]) == 1
    assert pool.ensure_rows(0, 13)
    assert pool.leak_check() == 0


def test_reset_and_status():
    pool = grouped()
    pool.assign_prompt(0, np.arange(20))
    pool.reset()
    assert pool.leak_check() == 0 and pool.used_pages == 0
    assert np.all(pool.window_group.table == -1)
    assert pool.status()["window_used_pages"] == 0


def test_what_a_window_group_refuses():
    with pytest.raises(ValueError, match="prefix cache"):
        grouped(prefix_cache=True)
    with pytest.raises(NotImplementedError, match="handoff"):
        grouped().adopt_slot(0, 2)


def test_device_pools_are_sized_by_group():
    import jax.numpy as jnp

    kinds = (True, True, False)
    pool = allocate_page_pool(
        3, 10, PS, 2, 64, jnp.bfloat16, window_layers=kinds, window_pages=4
    )
    assert [layer["k"].shape[0] for layer in pool] == [4, 4, 10]
    assert pool[0]["k"].shape[1:] == (1, PS, 128)
    nbytes = page_pool_bytes(
        3, 10, PS, 2, 64, 2, window_layers=kinds, window_pages=4
    )
    assert nbytes == 2 * (4 + 4 + 10) * PS * 128 * 2
    # one group: as before
    assert page_pool_bytes(3, 10, PS, 2, 64, 2) == 2 * 30 * PS * 128 * 2
    with pytest.raises(ValueError, match="window_layers has"):
        allocate_page_pool(3, 10, PS, 2, 64, jnp.bfloat16, window_layers=(True,), window_pages=4)
