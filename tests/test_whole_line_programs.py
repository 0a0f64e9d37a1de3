"""Whole-line geometries (``sectors_per_line = 1``, the default) compile
exactly the programs they compiled before sectored lines were modelled:
the optimised HLO of a paper-geometry sweep point, with its metadata
(op names, source locations) left out, hashes as it did then. So no
cell of the paper machine can move."""
import hashlib
import re

import numpy as np
import pytest

#: sha256 of each normalised executable, recorded before the sector
#: model was added (CPU backend, one device).
WANT = {
    "private": "0520744cb865fe15b43abcc4690145c3cf8b34dd2319aef34f4dfb17092b3971",
    "ata": "4fac71fbe9aae9abf7d45686df2329dbc32b9f87501f1670e378055a54428bcb",
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
