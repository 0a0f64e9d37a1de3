"""Whole-line geometries (``sectors_per_line = 1``, the default) compile
exactly the programs recorded here: the optimised HLO of a
paper-geometry sweep point, with its metadata (op names, source
locations) left out, hashes as recorded. So nothing meant for
sectored lines alone can move a cell of the paper machine."""
import hashlib
import re

import numpy as np
import pytest

#: sha256 of each normalised executable (CPU backend, one device),
#: recorded with the packed L2 state and its one scatter a round.
WANT = {
    "private": "b3f3a2d25915ccfd2ac0ad4b7dd5a9ad497830b7d802537378e282851048f817",
    "ata": "aec17973ae1211c95d27d65d345289dc2c60c075ae22ded9dff70e89439f64ca",
}

_METADATA = re.compile(r", metadata=\{[^}]*\}")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def normalised(text: str) -> str:
    """The HLO text without instruction metadata or the module's
    source-location tables."""
    out, skip = [], False
    for line in _METADATA.sub("", text).splitlines():
        if line in _TABLES:
            skip = True
        elif skip and not line:
            skip = False
            continue
        if not skip:
            out.append(line)
    return "\n".join(out)


def point_hash(arch: str) -> str:
    """Hash of the one executable a single-point paper-geometry grid of
    ``arch`` dispatches (8 rounds, 30 cores, m = 4, one device)."""
    from repro.core import PAPER_GEOMETRY, SweepGrid, sweep
    from repro.core.simulator import Trace
    rng = np.random.default_rng(0)
    shape = (8, PAPER_GEOMETRY.n_cores, 4)
    trace = Trace(rng.integers(0, 4096, shape).astype(np.int32),
                  rng.random(shape) < 0.1, 6.0)
    before = set(sweep._COMPILED_KEYS)
    SweepGrid([arch], [PAPER_GEOMETRY], [trace]).run(n_devices=1)
    new = [v for k, v in sweep._COMPILED_KEYS.items() if k not in before]
    (fn, abstract), = new
    text = fn.lower(abstract).compile().as_text()
    return hashlib.sha256(normalised(text).encode()).hexdigest()


@pytest.mark.parametrize("arch", sorted(WANT))
def test_whole_line_programs_are_unchanged(arch, monkeypatch):
    from repro.core import sweep
    monkeypatch.setattr(sweep, "_COMPILED_KEYS", {})
    monkeypatch.setattr(sweep, "_EXEC_MEMO", {})
    assert point_hash(arch) == WANT[arch]
