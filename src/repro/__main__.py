"""``python -m repro`` — the one front door to the IM drivers.

    PYTHONPATH=src python -m repro im --graph rmat:12 --k 10
    PYTHONPATH=src python -m repro serve --graph rmat:12 --queries 500
    PYTHONPATH=src python -m repro dryrun --im

Each subcommand forwards its remaining argv to the underlying launcher
(``repro.launch.im`` / ``repro.launch.serve_im`` / ``repro.launch.dryrun``),
which stay runnable directly for backward compatibility.
"""
from __future__ import annotations

import sys

_SUBCOMMANDS = {
    "im": "run DiFuseR end-to-end (seed selection + optional MC validation)",
    "serve": "build a sketch index once, serve a mixed query stream",
    "dryrun": "lower/compile production-mesh cells (no execution)",
}


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        lines = "\n".join(f"  {name:8s} {desc}"
                          for name, desc in _SUBCOMMANDS.items())
        print("usage: python -m repro <command> [args...]\n\n"
              f"commands:\n{lines}\n\n"
              "run `python -m repro <command> --help` for per-command flags")
        raise SystemExit(0 if argv else 2)
    cmd, rest = argv[0], argv[1:]
    if cmd in ("im", "serve"):
        from repro.launch.common import enable_compile_cache

        enable_compile_cache()
    if cmd == "im":
        from repro.launch.im import run

        run(rest)
    elif cmd == "serve":
        from repro.launch.serve_im import run

        run(rest)
    elif cmd == "dryrun":
        # dryrun owns sys.argv parsing (it must set XLA_FLAGS before jax
        # imports, so it cannot take argv as a parameter)
        sys.argv = [sys.argv[0]] + rest
        from repro.launch.dryrun import main as dryrun_main

        dryrun_main()
    else:
        raise SystemExit(f"unknown command {cmd!r}; options: "
                         f"{', '.join(_SUBCOMMANDS)}")


if __name__ == "__main__":
    main()
