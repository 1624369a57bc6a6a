"""The `attn_live_page_share` reader on hand-built windows."""
import types

import pytest

from tiny_cell import ROOT  # noqa: F401  (puts the repo on sys.path)
from bench.metrics import attn_live_page_share

ENGINE = {"max_slots": 4, "max_blocks": 8}


def _ctx(rows, device_kind="TPU v5 lite"):
    """rows: (kv_pages_used, decode_slots, prefill_slots) per iteration."""
    iters = [types.SimpleNamespace(kv_pages_used=u, decode_slots=d,
                                   prefill_slots=p) for u, d, p in rows]
    return types.SimpleNamespace(iters=iters, device_kind=device_kind,
                                 cell={"engine": ENGINE})


def test_mean_share_of_the_table_over_paged_steps():
    # 32 table entries; the idle iteration (no step) is left out
    ctx = _ctx([(4, 2, 0), (8, 1, 1), (0, 0, 0), (12, 0, 3)])
    assert attn_live_page_share.read(ctx) == pytest.approx(
        100.0 * (4 + 8 + 12) / (32 * 3))


def test_reports_nothing_without_a_paged_step():
    assert attn_live_page_share.read(_ctx([])) is None
    assert attn_live_page_share.read(_ctx([(0, 0, 0), (3, 0, 0)])) is None


def test_reports_nothing_in_a_rehearsal():
    assert attn_live_page_share.read(_ctx([(4, 2, 0)], None)) is None
