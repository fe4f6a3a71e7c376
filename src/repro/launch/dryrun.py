import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run of the distributed package-query step.

The two lines above MUST run before any other import (jax locks the device
count on first init).  512 host devices back both the 16x16 single-pod mesh
and the 2x16x16 multi-pod mesh.

Usage:
  python -m repro.launch.dryrun --pq [--multi-pod | --both-meshes]
                                [--out results/dryrun]
Writes one JSON per mesh with the step's collective bytes, its byte budget
and any contract violations.
"""
import argparse
import json
import traceback

from repro.launch.mesh import make_production_mesh


def run_pq_cell(*, multi_pod: bool, n: int = 1 << 24) -> dict:
    """Dry-run the paper's own technique: one distributed dual-simplex
    pivot — the pricing + exact-BFRT selection step (consuming MAINTAINED
    reduced costs, no c - y @ A recompute) and the post-pivot O(n/p)
    d-update step — on the full mesh.

    Delegates to the contract checker so the dry-run and CI prove the
    SAME invariants (zero update collectives, pq byte budget, dense-pass
    discipline, f32 cleanliness) instead of re-deriving them here."""
    from repro.analysis import contracts
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": "pq_step", "shape": f"m8_n{n}", "mesh": mesh_name}
    mesh = make_production_mesh(multi_pod=multi_pod)
    pq = contracts.check_pq_step(mesh, 8, n)
    upd = contracts.check_update_step(mesh, 8, n)
    viols = pq.violations + upd.violations
    rec.update(
        status="OK" if not viols else "CONTRACT_FAIL",
        compile_s=round(pq.wall_s + upd.wall_s, 1),
        n_devices=int(mesh.size),
        collectives=pq.record["collective_bytes"],
        collective_counts=pq.record["collective_counts"],
        budget_bytes=pq.record["budget_bytes"],
        budget_used_frac=pq.record["budget_used_frac"],
        dense_passes=pq.record["dense_passes"],
        update_collectives=upd.record["collective_bytes"],
        update_collective_counts=upd.record["collective_counts"],
        violations=[v.format() for v in viols],
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pq", action="store_true",
                    help="dry-run the distributed package-query step")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    if not args.pq:
        ap.error("nothing to dry-run: pass --pq")

    os.makedirs(args.out, exist_ok=True)
    rc = 0
    for mp in ([False, True] if args.both_meshes else [args.multi_pod]):
        mesh_name = "2x16x16" if mp else "16x16"
        try:
            rec = run_pq_cell(multi_pod=mp)
        except (ValueError, TypeError, KeyError, RuntimeError,
                NotImplementedError, OSError) as e:
            # XlaRuntimeError subclasses RuntimeError; anything else
            # (assertion, keyboard interrupt) should still crash loudly
            rec = {"arch": "pq_step", "mesh": mesh_name, "status": "FAIL",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        if rec["status"] != "OK":
            rc = 1
        with open(os.path.join(args.out,
                               f"pq_step__{mesh_name}.json"), "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[dryrun] pq_step {mesh_name}: {rec['status']} "
              + rec.get("error", "")[:200], flush=True)
        for v in rec.get("violations", ()):
            print(f"  {v}", flush=True)
        if rec["status"] in ("OK", "CONTRACT_FAIL"):
            print(f"  coll_bytes/dev={rec['collectives'].get('total', 0):.3e}"
                  f" budget_used={rec['budget_used_frac']:.2f}")
    return rc

if __name__ == "__main__":
    raise SystemExit(main())
