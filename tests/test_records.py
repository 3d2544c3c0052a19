"""The tree's records agree with the tree: no document or program cites
the removed benchmark script or its outputs, the chip smoke runs the
benchmark's model, and every knob a document tabulates exists."""

import glob
import json
import os
import re
import subprocess

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# history, the account of speed, the plan and the issue may name what
# was removed; this file names it to look for it
_MAY_NAME_IT = {"CHANGES.md", "PERF.md", "ROADMAP.md", "ISSUE.md",
                "tests/test_records.py"}
_REMOVED = re.compile(r"bench\.py|BENCH_r0|MULTICHIP_")


def _tracked(*suffixes):
    """The files git would commit. A checkout without git (the driver's
    holds the committed files and nothing else) is walked whole."""
    try:
        out = subprocess.run(
            ["git", "ls-files", "--", *("*" + s for s in suffixes)],
            cwd=REPO, capture_output=True, text=True, check=True,
            timeout=60).stdout
        paths = out.splitlines()
    except (OSError, subprocess.SubprocessError):
        paths = [os.path.relpath(os.path.join(d, name), REPO)
                 for d, _dirs, names in os.walk(REPO) for name in names]
    return [p for p in paths if p.endswith(suffixes)
            and os.path.exists(os.path.join(REPO, p))]


def test_nothing_cites_the_removed_benchmark():
    cited = []
    for path in _tracked(".md", ".py"):
        if path in _MAY_NAME_IT:
            continue
        with open(os.path.join(REPO, path), encoding="utf-8") as f:
            cited += [f"{path}:{n}: {line.strip()}"
                      for n, line in enumerate(f, 1) if _REMOVED.search(line)]
    assert not cited, "\n".join(cited)


def test_chip_smoke_runs_the_benchmarks_model():
    import chip_smoke
    from ray_tpu.models import config_from_hf

    widths = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "rope_theta")
    files = sorted(glob.glob(os.path.join(
        REPO, "benchmark", "configs", "mistral-7b-*.json")))
    assert files
    for path in files:
        with open(path) as f:
            cfg = config_from_hf(json.load(f), max_seq_len=4096)
        assert {k: chip_smoke.FLAGSHIP[k] for k in widths} == \
            {k: getattr(cfg, k) for k in widths}, path


def test_documented_knobs_exist():
    from ray_tpu._private.config import Config

    tables, unknown = 0, []
    for path in sorted(glob.glob(os.path.join(REPO, "docs", "*.md"))):
        in_table = False
        with open(path, encoding="utf-8") as f:
            for n, line in enumerate(f, 1):
                cells = [c.strip() for c in line.split("|")[1:-1]]
                if not line.startswith("|"):
                    in_table = False
                elif cells[0].startswith(("knob", "config knob")):
                    in_table, tables = True, tables + 1
                elif in_table and not set(cells[0]) <= set("-: "):
                    unknown += [f"{os.path.relpath(path, REPO)}:{n}: {name}"
                                for name in re.findall(r"`(\w+)`", cells[0])
                                if name not in Config._DEFS]
    assert tables >= 6, tables
    assert not unknown, "\n".join(unknown)
