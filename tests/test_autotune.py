"""Block autotuner: shape classing, heuristic properties, cache behavior.

``get_block_config`` is the lookup every kernel launch goes through, so
its two invariants matter most: it never times anything (CI interpret
mode must stay deterministic), and the same cache key always resolves to
the same config — cold (fresh process view of the on-disk table) or warm
(in-memory).  ``tune`` is exercised with an injected deterministic
``measure`` so tests never depend on wall-clock.
"""
import json

import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (
    BlockConfig,
    VMEM_BUDGET_BYTES,
    candidate_configs,
    cache_key,
    get_block_config,
    heuristic_config,
    shape_class,
    tune,
)


@pytest.fixture()
def cache_file(tmp_path):
    """Fresh on-disk cache per test; memory view cleared before and after."""
    autotune.clear_memory_cache()
    yield str(tmp_path / "autotune.json")
    autotune.clear_memory_cache()


# ---------------------------------------------------------------------------
# Shape classes + heuristic
# ---------------------------------------------------------------------------

def test_shape_classes():
    assert shape_class(1) == "decode_m1"
    assert shape_class(2) == "small_m"
    assert shape_class(32) == "small_m"
    assert shape_class(33) == "prefill"
    assert shape_class(1024) == "prefill"
    assert shape_class(1, expert=True) == "expert"
    assert shape_class(256, expert=True) == "expert"


def test_heuristic_decode_is_single_row():
    cfg = heuristic_config("decode_m1", 1, 1024, 512, n_p=8, gs=2)
    assert cfg.block_m == 1
    assert cfg.source == "heuristic"


def test_heuristic_prefill_tiles_exceed_old_caps():
    """The old resolver capped every launch at 8x128; the shape-class
    heuristic must hand prefill shapes materially larger tiles."""
    cfg = heuristic_config("prefill", 256, 1024, 512, n_p=8, gs=2)
    assert cfg.block_m > 8 and cfg.block_n > 128


def test_heuristic_small_shapes_get_single_tile():
    """Blocks never exceed the padded dims (one launch covers the GEMM)."""
    cfg = heuristic_config("prefill", 40, 64, 130, n_p=4, gs=2)
    assert cfg.block_m == 40  # _round_up(40, 8)
    assert cfg.block_n == 256  # _round_up(130, 128)


@pytest.mark.parametrize("cls,m", [("decode_m1", 1), ("small_m", 16),
                                   ("prefill", 256), ("expert", 64)])
def test_heuristic_respects_vmem_budget(cls, m):
    k, n, n_p, gs = 8192, 8192, 8, 4
    cfg = heuristic_config(cls, m, k, n, n_p=n_p, gs=gs)
    bk = -(-k // n_p)
    used = autotune._vmem_bytes(cfg.block_m, cfg.block_n, bk, gs, n_p,
                                cfg.exp_layout, n)
    assert used <= VMEM_BUDGET_BYTES


def test_candidates_deterministic_and_feasible():
    a = candidate_configs("prefill", 256, 1024, 512, n_p=8, gs=2)
    b = candidate_configs("prefill", 256, 1024, 512, n_p=8, gs=2)
    assert a == b and len(a) > 1
    assert all(c.source == "tuned" for c in a)
    # decode_m1 pins the fast-path row; expert pins the blocked layout
    assert {c.block_m for c in
            candidate_configs("decode_m1", 1, 1024, 512, n_p=8, gs=2)} \
        == {1}
    assert {c.exp_layout for c in
            candidate_configs("expert", 64, 512, 256, n_p=8, gs=2)} \
        == {"blocked"}


# ---------------------------------------------------------------------------
# Cache determinism
# ---------------------------------------------------------------------------

def _fake_measure(cfg, m, k, n, **kw):
    """Deterministic cost model: prefer bn=256 then bm=64, no clock."""
    return abs(cfg.block_n - 256) + abs(cfg.block_m - 64) / 10.0


def test_get_block_config_never_times(cache_file, monkeypatch):
    """The launch-path lookup must not touch the measurement path."""
    def boom(*a, **k):
        raise AssertionError("get_block_config invoked the timer")
    monkeypatch.setattr(autotune, "_default_measure", boom)
    cfg = get_block_config(256, 1024, 512, n_p=8, gs=2, path=cache_file)
    assert cfg.source == "heuristic"


def test_tune_same_key_same_config_cold_vs_warm(cache_file):
    """tune -> warm lookup == cold (re-read from disk) lookup, and a
    second tune with the same measurements lands the same winner."""
    win1 = tune(256, 1024, 512, n_p=8, gs=2, path=cache_file,
                measure=_fake_measure)
    warm = get_block_config(256, 1024, 512, n_p=8, gs=2, path=cache_file)
    autotune.clear_memory_cache()  # force re-read of the on-disk table
    cold = get_block_config(256, 1024, 512, n_p=8, gs=2, path=cache_file)
    assert warm == cold
    assert warm.source == "tuned"
    assert (warm.block_m, warm.block_n) == (win1.block_m, win1.block_n)
    win2 = tune(256, 1024, 512, n_p=8, gs=2, path=cache_file,
                measure=_fake_measure)
    assert win1 == win2


def test_tuned_entry_applies_per_key_only(cache_file):
    """A winner tuned for (prefill, np=8, gs=2) must not leak onto other
    shape classes or other (n_p, gs) keys."""
    tune(256, 1024, 512, n_p=8, gs=2, path=cache_file,
         measure=_fake_measure)
    same_cls = get_block_config(512, 2048, 512, n_p=8, gs=2,
                                path=cache_file)
    assert same_cls.source == "tuned"
    other_np = get_block_config(256, 1024, 512, n_p=4, gs=2,
                                path=cache_file)
    assert other_np.source == "heuristic"
    decode = get_block_config(1, 1024, 512, n_p=8, gs=2, path=cache_file)
    assert decode.source == "heuristic" and decode.block_m == 1


def test_tuned_winner_clamps_to_smaller_shape(cache_file):
    """A winner tuned at a large representative shape stays legal on a
    smaller same-class shape (blocks never exceed the padded dims)."""
    tune(256, 1024, 512, n_p=8, gs=2, path=cache_file,
         measure=_fake_measure)
    small = get_block_config(40, 64, 130, n_p=8, gs=2, path=cache_file)
    assert small.source == "tuned"
    assert small.block_m <= 40 and small.block_n <= 256


def test_cache_file_versioned_and_keyed(cache_file):
    tune(1, 1024, 512, n_p=8, gs=2, path=cache_file,
         measure=_fake_measure)
    with open(cache_file) as f:
        payload = json.load(f)
    assert payload["version"] == autotune.CACHE_VERSION
    key = cache_key("decode_m1", 8, 2)
    assert key in payload["entries"]
    assert payload["entries"][key]["block_m"] == 1


def test_corrupt_cache_falls_back_to_heuristic(cache_file):
    with open(cache_file, "w") as f:
        f.write("{not json")
    cfg = get_block_config(256, 1024, 512, n_p=8, gs=2, path=cache_file)
    assert cfg.source == "heuristic"


def test_env_var_picks_cache_path(tmp_path, monkeypatch):
    p = str(tmp_path / "env-cache.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", p)
    assert autotune.cache_path() == p
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE")
    # Unset: no table at all (nothing outside the checkout is read), so
    # every launch takes the heuristic and tuning needs an explicit path.
    assert autotune.cache_path() is None
    autotune.clear_memory_cache()
    assert get_block_config(256, 1024, 512, n_p=8, gs=2).source \
        == "heuristic"
    with pytest.raises(ValueError, match="REPRO_AUTOTUNE_CACHE"):
        tune(256, 1024, 512, n_p=8, gs=2, measure=_fake_measure)


def test_resolved_table_covers_all_classes(cache_file, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", cache_file)
    autotune.clear_memory_cache()
    table = autotune.resolved_table()
    assert set(table) == set(autotune.SHAPE_CLASSES)
    for rec in table.values():
        assert {"block_m", "block_n", "exp_layout",
                "blocks_source"} <= set(rec)
