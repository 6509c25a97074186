"""Dry-run sweep driver: one subprocess per (arch x shape x mesh) cell.

Per-cell isolation keeps one failed compile from killing the sweep and
bounds memory growth.  The dry run fakes 512 host devices, so it is a
CPU-only tool: every child runs with ``JAX_PLATFORMS=cpu`` and never takes
an accelerator.  Single-pod cells run with differential cost probes
(they feed the roofline table); multi-pod cells prove lowering/compile +
memory only (the brief's roofline table is single-pod).

  PYTHONPATH=src python -m repro.launch.sweep --mesh single
  PYTHONPATH=src python -m repro.launch.sweep --mesh multi
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import repro.configs as configs
from repro.configs.shapes import SHAPES, applicable

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--force", action="store_true",
                    help="re-run cells that already have ok artifacts")
    args = ap.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    for mesh in meshes:
        for arch in configs.list_archs():
            for shape in SHAPES:
                cells.append((arch, shape, mesh))

    done = failed = skipped = 0
    for arch, shape, mesh in cells:
        tag = f"{arch}__{shape}__{mesh}"
        art = ARTIFACTS / f"{tag}.json"
        cfg = configs.get(arch)
        ok, reason = applicable(cfg, SHAPES[shape])
        if not ok:
            ARTIFACTS.mkdir(parents=True, exist_ok=True)
            art.write_text(json.dumps({
                "arch": arch, "shape": shape, "mesh": mesh,
                "status": "skipped", "reason": reason}, indent=2))
            skipped += 1
            print(f"[skip] {tag}: {reason}", flush=True)
            continue
        if art.exists() and not args.force:
            try:
                prev = json.loads(art.read_text())
                if prev.get("status") == "ok" and (
                        mesh == "multi" or "extrapolated" in prev):
                    done += 1
                    print(f"[cached] {tag}", flush=True)
                    continue
            except Exception:
                pass
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--mesh", mesh]
        if mesh == "multi":
            cmd.append("--no-probes")
        t0 = time.time()
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=args.timeout,
                                  env={**os.environ, "JAX_PLATFORMS": "cpu"})
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            rc = -9
        dt = time.time() - t0
        status = "ok" if rc == 0 else "FAIL"
        if rc != 0:
            failed += 1
            ARTIFACTS.mkdir(parents=True, exist_ok=True)
            if not art.exists():
                art.write_text(json.dumps({
                    "arch": arch, "shape": shape, "mesh": mesh,
                    "status": "error",
                    "error": f"subprocess rc={rc}"}, indent=2))
        else:
            done += 1
        print(f"[{status}] {tag} ({dt:.0f}s)", flush=True)
    print(f"sweep complete: ok={done} failed={failed} skipped={skipped}",
          flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
