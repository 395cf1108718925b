"""What the perfbench tests share: a copy of the benchmark into which a
family that is no U-Net (``data/toy_family``) is laid by new files and new
entries alone, the way a later PR has to add its own."""

import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TOY = Path(__file__).parent / "data" / "toy_family"
ENTRIES = "entries.json"        # what the toy family appends to BENCHMARK.json


def lay_in_toy_family(dest: Path) -> Path:
    """``BENCHMARK.json`` and ``perfbench/`` of the repository copied under
    ``dest``, then the toy family: each of its files as a file that was not
    there, each of its entries appended under a name that was not there."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(ROOT / "perfbench", dest / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dest / "tests" / "perfbench").mkdir(parents=True)  # the second of `paths`
    for src in sorted(TOY.rglob("*")):
        if not src.is_file() or "__pycache__" in src.parts \
                or src.name == ENTRIES:
            continue
        target = dest / "perfbench" / src.relative_to(TOY)
        assert not target.exists(), f"{target} is a file the benchmark has"
        shutil.copy(src, target)
    doc = json.loads((dest / "BENCHMARK.json").read_text())
    for key, entries in json.loads((TOY / ENTRIES).read_text()).items():
        names = {e["name"] for e in doc[key]}
        assert not names & {e["name"] for e in entries}
        doc[key].extend(entries)
    (dest / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return dest


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory) -> Path:
    return lay_in_toy_family(tmp_path_factory.mktemp("toy_family"))
