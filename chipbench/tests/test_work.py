"""Work counts at the published widths, against hand counts: the scan's
(``work.py``) and the query phase's, which the tower's reference counts
(``references/list_dual_encoder.py``, ``query_flops``)."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import work
from chipbench.references import list_dual_encoder as ref

BENCH = pathlib.Path(__file__).resolve().parents[1]


def model():
    return json.loads((BENCH / "configs" / "geoglue-bf16.json").read_text())[
        "model"]


def test_tower_is_170_mflop_per_token_per_pass():
    # 12 layers x 2 x (4 x 768^2 + 2 x 768 x 3072)
    m = model()
    assert ref.tower_dense_flops_per_token(m) == 169_869_312
    # one more token: its pass, and its attention (4 x 768 x 12 at n = 1)
    assert (ref.query_flops(m, [1]) - ref.query_flops(m, [0])
            == 169_869_312 + 4 * 768 * 12)


def test_query_flops_add_attention_head_mixing_and_router():
    m = model()
    n = 10
    want = (n * 169_869_312 + 4 * n * n * 768 * 12 + 2 * 768 * 768
            + 2 * (768 * 64 + 64 * 2)
            + 2 * (770 * 512 + 512 * 512 + 512 * 300))
    assert ref.query_flops(m, [n]) == want
    assert ref.query_flops(m, [n, n]) == 2 * want


@pytest.mark.parametrize("precision,row", [("bf16", 768 * 2 + 8 + 4),
                                           ("int8", 768 + 8 + 4 + 4),
                                           ("f32", 768 * 4 + 8 + 4)])
def test_bytes_per_valid_row(precision, row):
    assert work.scan_row_bytes(precision, 768) == row


def test_scan_work_counts_distinct_clusters_once():
    counts = np.array([100, 200, 300])
    routes = np.array([[0], [0], [2]])      # cluster 0 routed twice
    flops, nbytes = work.scan_work(routes, counts, d=768, precision="bf16")
    assert flops == 2 * 768 * (100 + 100 + 300)
    assert nbytes == (100 + 300) * 1548


def test_bound_is_the_larger_of_compute_and_memory():
    assert work.bound_seconds(2e12, 1e9, peak_flops=1e12,
                              hbm_bytes_per_s=1e9) == 2.0
    assert work.bound_seconds(1e12, 4e9, peak_flops=1e12,
                              hbm_bytes_per_s=1e9) == 4.0
