"""A throw-away copy of the benchmark with a tiny configuration, mix,
cell and per-layer metric of each kind dropped in as new files and new
manifest entries: what a later PR does to add a cell, at a size the CPU
runs in a second. Nothing that is there is edited."""

from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_MODEL = {
    "reference": "dense_transformer",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "sliding_window": 32, "torch_dtype": "float32",
}

CONFIGS = {
    "tiny-train": {
        **TINY_MODEL, "kind": "train",
        "program": {"mesh": [1, 1, 1], "attn": "ulysses",
                    "attn_impl": "reference", "remat": False,
                    "lr": 0.01, "donate": True},
        "limits": {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
                   "change_norm_gap": 1e-2},
    },
    "tiny-serve": {
        **TINY_MODEL, "kind": "serve",
        "program": {"slots": 4, "n_inner": 4, "quantize_kv": True,
                    "page_tokens": 8, "prompt_chunk": 16, "max_prompt": 64,
                    "attn": "ulysses", "attn_impl": "reference"},
        "limits": {"logit_gap_worst": 0.03, "logit_gap_mean": 4e-4},
    },
}

TRAFFIC = {
    "tiny_seq64": {"batch": 2, "seq": 64, "distinct_batches": 2,
                   "reference_steps": 3, "warm_steps": 1,
                   "trace_seconds": 1},
    "tiny_backlog": {
        "round": 8, "rounds": 156, "warm_rounds": 2, "window_rounds": 150,
        "check_requests": 10,
        "trace_seconds": 1,
        "classes": [
            {"name": "short", "share": 0.75,
             "prompt": [4, 20, "log_uniform"],
             "output": [6, 16, "log_uniform"]},
            {"name": "long", "share": 0.25,
             "prompt": [30, 60, "log_uniform"],
             "output": [4, 8, "log_uniform"]},
        ],
    },
}

CELLS = [
    ("tiny_train", "tiny-train", "tiny_seq64"),
    ("tiny_serve", "tiny-serve", "tiny_backlog"),
]

THROWAWAY_METRIC = '''"""A per-layer metric dropped in by a later PR."""


def read(run):
    return float(run.attempted)
'''


def make_tiny_checkout(dst: Path) -> Path:
    """Copy BENCHMARK.json and chipbench/ to ``dst`` and add the tiny
    files and entries. Returns ``dst``."""
    dst = Path(dst)
    shutil.copytree(REPO / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, cfg in CONFIGS.items():
        path = dst / "chipbench" / "configs" / f"{name}.json"
        path.write_text(json.dumps(cfg))
        manifest["configs"].append({
            "name": name, "source": "tests/chipbench/_tiny.py",
            "file": f"chipbench/configs/{name}.json", "reduced": [],
            "why": "throw-away",
        })
    for name, t in TRAFFIC.items():
        (dst / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(t))
    (dst / "chipbench" / "metrics" / "tiny_attempted.py").write_text(
        THROWAWAY_METRIC)
    for cell, config, traffic in CELLS:
        manifest["workloads"].append({
            "name": cell, "config": config, "traffic": traffic,
            "chips": 1, "why": "throw-away",
        })
    # a tiny cell reports what the committed cell of its kind reports
    like = {"train_sc2_8k": "tiny_train", "serve_sc2_chat": "tiny_serve"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for big, tiny in like.items():
            if big in m.get("workloads", ()):
                m["workloads"].append(tiny)
    manifest["per_layer"].append({
        "name": "tiny_attempted", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tok_s", "workloads": ["tiny_train"],
    })
    (dst / "BENCHMARK.json").write_text(json.dumps(manifest))
    return dst


# -- the guard: what a later PR adds, at the END of every list ----------------
#
# One configuration, one serving mix, one cell and one per-layer metric
# as new files and new entries behind everything BENCHMARK.json has,
# the cell's name appended to the lists of the metrics its kind
# reports. Every manifest-reading test of tests/chipbench runs on the
# committed benchmark and on this copy (the ``checkout`` fixture of
# conftest.py), so a test that holds an entry to a place, or a list of
# names to what it was, fails here on the CPU and not in the PR that
# adds the next cell. Unlike ``tiny_backlog`` the mix keeps every rule
# of test_traffic.py; the cell is never run.

GUARD_CONFIG = {
    **CONFIGS["tiny-serve"],
    "program": {**CONFIGS["tiny-serve"]["program"], "max_prompt": 512,
                "max_context": 640},
}

GUARD_MIX = {
    "arrivals": "backlog, as the committed serving mixes",
    "round": 8, "rounds": 64, "warm_rounds": 2, "window_rounds": 58,
    "check_requests": 4, "trace_seconds": 1,
    "classes": [
        {"name": "short", "share": 0.5,
         "prompt": [16, 128, "log_uniform"],
         "output": [32, 128, "log_uniform"]},
        {"name": "long", "share": 0.5,
         "prompt": [256, 512, "log_uniform"],
         "output": [8, 32, "log_uniform"]},
    ],
}

GUARD = {"config": "guard-serve", "traffic": "guard_backlog",
         "cell": "guard_serve", "metric": "guard_attempted",
         "like": "serve_sc2_chat"}


def with_additions(manifest: dict) -> dict:
    """``manifest`` with the guard's four entries appended, each at the
    end of its list; nothing that is there is touched."""
    out = copy.deepcopy(manifest)
    out["configs"].append({
        "name": GUARD["config"], "source": "tests/chipbench/_tiny.py",
        "file": f"chipbench/configs/{GUARD['config']}.json", "reduced": [],
        "why": "throw-away",
    })
    out["workloads"].append({
        "name": GUARD["cell"], "config": GUARD["config"],
        "traffic": GUARD["traffic"], "chips": 1, "why": "throw-away",
    })
    for m in out["end_to_end"] + out["per_layer"]:
        if GUARD["like"] in m.get("workloads", ()):
            m["workloads"].append(GUARD["cell"])
    out["per_layer"].append({
        "name": GUARD["metric"], "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "server",
        "moves": "serve_tok_s", "workloads": [GUARD["cell"]],
    })
    return out


def make_guard_checkout(dst: Path) -> Path:
    """Copy BENCHMARK.json and the directories it names under ``paths``
    to ``dst`` and add the guard's files and entries. Returns ``dst``."""
    dst = Path(dst)
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for base in manifest["paths"]:
        shutil.copytree(REPO / base, dst / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = dst / "chipbench"
    (bench / "configs" / f"{GUARD['config']}.json").write_text(
        json.dumps(GUARD_CONFIG))
    (bench / "traffic" / f"{GUARD['traffic']}.json").write_text(
        json.dumps(GUARD_MIX))
    (bench / "metrics" / f"{GUARD['metric']}.py").write_text(
        THROWAWAY_METRIC)
    (dst / "BENCHMARK.json").write_text(
        json.dumps(with_additions(manifest), indent=2) + "\n")
    return dst


def serving_cells(root: Path, manifest: dict) -> list[str]:
    """The cells of ``manifest`` whose configuration's ``kind`` (its
    file under ``root``) is a serving one, in the manifest's order."""
    kind = {c["name"]: json.loads((Path(root) / c["file"]).read_text())["kind"]
            for c in manifest["configs"]}
    return [w["name"] for w in manifest["workloads"]
            if kind[w["config"]].startswith("serve")]


def serving_mixes(root: Path, manifest: dict) -> list[str]:
    """The serving cells' ``traffic``, each once, in the manifest's
    order: the serving mixes are what the manifest says they are."""
    cells = set(serving_cells(root, manifest))
    return list(dict.fromkeys(
        w["traffic"] for w in manifest["workloads"] if w["name"] in cells))


def stands_after(names: list, later, earlier) -> bool:
    """Whether ``later`` is in ``names`` behind every one of
    ``earlier`` (a name or several): what "was appended" means once
    further entries may follow."""
    earlier = [earlier] if isinstance(earlier, str) else list(earlier)
    return all(names.index(later) > names.index(e) for e in earlier)
