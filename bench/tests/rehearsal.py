"""A scratch checkout of the benchmark at a tiny size, for CPU tests.

``checkout(tmp)`` copies ``bench/`` beside a ``BENCHMARK.json`` that adds
one cell built only from new files: a tiny configuration
(``bench/configs/tiny.json``, dense or with routed experts, judged by
``bench/reference.py`` or by a copy of it under a name of its own), a tiny
mix (``bench/traffic/tiny-closed.json``) and its limits
(``bench/limits/tiny.closed.json``), and lists the new cell under every
metric that the full-size cell reports.  No file that the
benchmark already has is edited.  ``load_cell`` imports that
checkout's ``cell.py`` with the look for a chip replaced by JAX's own
devices, so the rest of a run drives the CPU.
"""
from __future__ import annotations

import gzip
import importlib.util
import itertools
import json
import shutil
import sys
from pathlib import Path
from typing import Optional

REPO = Path(__file__).resolve().parents[2]
FULL_CELL = "granite-8b-l12.offline"

TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            head_dim=16, d_ff=128, vocab_size=512, max_seq_len=64,
            published={"num_layers": 4})
TINY_MOE = dict(num_experts=4, top_k=2, expert_d_ff=96, capacity_factor=4.0)

MIX = dict(kind="closed", b_max=4, max_len=64, queue=4, prompt_lens=[8, 16],
           prompt_probs=[0.5, 0.5], out_median=8, out_sigma=0.5, out_min=4,
           out_max=16, stagger=True, requests=256, shape_seed=0,
           check_tokens=40)

FIXTURE = REPO / "bench" / "tests" / "fixtures" / "window.xplane.pb.gz"

_ids = itertools.count()


def unpack_fixture(tmp: Path) -> str:
    """The profiler trace recorded on a TPU v5e (a traced window of the
    tiny dense cell, made with ``bench/cell.py``'s own profiler options),
    written out under ``tmp``; returns its path."""
    path = tmp / "window.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return str(path)


def tiny_config(moe: bool) -> dict:
    """The granite configuration at a tiny size; with ``moe``, top-2 routed
    experts in place of the dense FFN and an untied head, as Mixtral has."""
    c = json.loads((REPO / "bench" / "configs" / "granite-8b-l12.json").read_text())
    c.update(TINY, name="tiny")
    if moe:
        c.update(ffn="moe", tie_embeddings=False, moe=dict(TINY_MOE))
    return c


def checkout(tmp: Path, limit: float = 0.05, moe: bool = False,
             reference: Optional[str] = None,
             limits: Optional[dict] = None) -> str:
    """Write the scratch checkout under ``tmp``; returns the new cell's
    name.  ``reference``: a new path, from the checkout's root, to which
    ``bench/reference.py`` is copied for the tiny configuration to name;
    ``limits``: the limits file, ``{"served_gap": limit}`` if not given."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    config = tiny_config(moe)
    if reference:
        shutil.copyfile(REPO / "bench" / "reference.py", tmp / reference)
        config["reference"] = reference
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    (tmp / "bench" / "traffic" / "tiny-closed.json").write_text(json.dumps(MIX))
    name = "tiny.closed"
    (tmp / "bench" / "limits" / f"{name}.json").write_text(
        json.dumps(limits if limits is not None else {"served_gap": limit}))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "tiny test size",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "tiny test size"})
    bench["workloads"].append({"name": name, "config": "tiny",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "tiny test size"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if FULL_CELL in m.get("workloads", []):
            m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def load_cell(root: Path, stub_chip: bool = True):
    """Import ``root``'s ``bench/cell.py`` with the compilation cache left
    as it is and (``stub_chip``) the look for a chip replaced by the
    devices JAX has here."""
    name = f"bench_cell_{next(_ids)}"
    spec = importlib.util.spec_from_file_location(name, root / "bench" / "cell.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)

    def devices(chips):
        import jax
        return jax.devices()[:chips]

    def jax_as_is():
        import jax
        return jax

    if stub_chip:
        mod.require_accelerator = devices
    mod.setup_jax = jax_as_is
    return mod
