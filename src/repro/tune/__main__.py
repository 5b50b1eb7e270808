"""``python -m repro.tune`` — run the empirical install-time sweep.

Examples::

    python -m repro.tune --letters S --trans NN --quick
    python -m repro.tune --letters SD --trans NN,NT --max-dim 1024 --compiled
    python -m repro.tune --show        # print the active profile, no sweep

Writes the versioned DeviceProfile JSON to the per-device default path
(override with --out / $REPRO_TUNE_CACHE) and merges with any existing
profile unless --no-merge.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro import runtime
from repro.tune import classes as classes_mod
from repro.tune import profile as profile_mod
from repro.tune import search


def _parse_letters(s: str):
    letters = [c for c in s.upper().replace(",", "") if not c.isspace()]
    for c in letters:
        if c not in ("S", "D", "C", "Z", "H"):
            raise argparse.ArgumentTypeError(f"unknown BLAS letter {c!r}")
    return letters


def _parse_trans(s: str):
    out = [t.strip().upper() for t in s.split(",") if t.strip()]
    for t in out:
        if t not in ("NN", "NT", "TN", "TT"):
            raise argparse.ArgumentTypeError(f"unknown transposition {t!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.tune",
        description="Empirical IAAT tuning sweep -> persistent DeviceProfile")
    ap.add_argument("--letters", type=_parse_letters, default=["S"],
                    help="BLAS dtype letters, e.g. S, SD, S,D (default S)")
    ap.add_argument("--trans", type=_parse_trans, default=["NN"],
                    help="comma-separated transpositions (default NN)")
    ap.add_argument("--min-dim", type=int, default=8)
    ap.add_argument("--max-dim", type=int, default=512)
    ap.add_argument("--top", type=int, default=4,
                    help="candidates timed per class after the prior prune")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--quick", action="store_true",
                    help="cube classes only, max-dim 128, reps 3, top 2 "
                         "(CI / interpret-mode smoke)")
    ap.add_argument("--compiled", action="store_true",
                    help="time compiled kernels even where the platform "
                         "would interpret them (default: compiled on a "
                         "TPU, interpret mode elsewhere)")
    ap.add_argument("--out", default=None,
                    help="profile path (default: per-device cache path)")
    ap.add_argument("--no-merge", action="store_true",
                    help="overwrite instead of merging an existing profile")
    ap.add_argument("--show", action="store_true",
                    help="print the profile at the target path and exit")
    args = ap.parse_args(argv)

    interpret = runtime.pallas_interpret(False if args.compiled else None)
    mode = "interpret" if interpret else "compiled"
    path = args.out or profile_mod.default_profile_path(mode=mode)
    if args.show:
        # without --out, show what tuned dispatch would actually load
        # (compiled preferred over interpret)
        show_path = args.out or profile_mod.find_default_profile() or path
        try:
            prof = profile_mod.DeviceProfile.load(show_path)
        except (OSError, ValueError, KeyError) as e:
            print(f"no profile at {show_path}: {e}", file=sys.stderr)
            return 1
        print(json.dumps(prof.to_json(), indent=1, sort_keys=True))
        return 0

    if args.quick:
        args.max_dim = min(args.max_dim, 128)
        args.reps = min(args.reps, 3)
        args.top = min(args.top, 2)

    def progress(sc, entry):
        winner = "pallas" if entry.prefer_pallas else "xla"
        sig = entry.sig.name if entry.sig else "-"
        pal = f"{entry.pallas.median_us:9.1f}" if entry.pallas else "     fail"
        xla = f"{entry.xla.median_us:9.1f}" if entry.xla else "     fail"
        print(f"  {sc.key:<18} pallas {pal}us  xla {xla}us  "
              f"-> {winner:<6} {sig}")

    n_classes = len(classes_mod.classes_up_to(
        args.letters, args.trans, args.max_dim, min_dim=args.min_dim,
        cube_only=args.quick))
    print(f"tuning {n_classes} size classes "
          f"({''.join(args.letters)} x {','.join(args.trans)}, "
          f"dims {args.min_dim}..{args.max_dim}, {mode} mode)")
    prof = search.sweep(args.letters, args.trans,
                        min_dim=args.min_dim, max_dim=args.max_dim,
                        cube_only=args.quick, top=args.top,
                        warmup=args.warmup, reps=args.reps,
                        interpret=interpret, progress=progress)
    if not args.no_merge:
        try:
            prof = profile_mod.DeviceProfile.load(path).merge(prof)
        except (OSError, ValueError, KeyError):
            pass        # absent or unusable existing profile: overwrite
    written = prof.save(path)
    profile_mod.clear_active_profile()   # next tuned dispatch sees the update
    n_pallas = sum(e.prefer_pallas for e in prof.entries.values())
    print(f"wrote {written} ({len(prof)} classes, "
          f"{n_pallas} prefer pallas)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
