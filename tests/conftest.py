"""Shared test fixtures.

`SHARK_SPILL_DIR` isolation: the storage tier (DESIGN.md §12) writes spill
segments to the directory named by this env var (falling back to a private
mkdtemp).  Tests must never share spill state with each other or with
whatever the developer's shell exports, so every test gets a fresh tmpdir.
"""

import pytest


@pytest.fixture(autouse=True)
def _isolated_spill_dir(tmp_path, monkeypatch):
    spill = tmp_path / "spill"
    spill.mkdir()
    monkeypatch.setenv("SHARK_SPILL_DIR", str(spill))
    yield str(spill)


# Above any test table's partition rows: no partition reaches the
# whole-stage gate, so the compiled backend runs segment at a time.
_UNFUSED_MIN_ROWS = 1 << 40


def fusion_pde(fused: bool, **kw):
    """PDEConfig for a fused or an unfused compiled session (DESIGN.md §14).

    Fused: every partition passes the `stage_fusion_min_rows` gate, and the
    compiled-segment floor drops to one row so that tiny test partitions run
    the stage program rather than the numpy rung.  Unfused: no partition
    passes the gate.  The row threshold is the only switch between them."""
    from repro.core.pde import PDEConfig
    if fused:
        return PDEConfig(stage_fusion_min_rows=0,
                         segment_min_compiled_rows=1, **kw)
    return PDEConfig(stage_fusion_min_rows=_UNFUSED_MIN_ROWS, **kw)
