"""Subcommand dispatch: ``python -m photon_ml_tpu <driver> [args...]``.

The four reference entry points (SURVEY.md §2.5) under one module runner —
``train_glm``, ``train_game``, ``score_game``, ``build_index`` — plus
``serve_game`` (online serving) and ``refresh_game`` (the continuous-
training incremental refresh), neither of which the reference shipped.
"""

from __future__ import annotations

import sys

_DRIVERS = {
    "train_glm": "photon_ml_tpu.cli.train_glm",
    "train_game": "photon_ml_tpu.cli.train_game",
    "refresh_game": "photon_ml_tpu.cli.refresh_game",
    "join_feedback": "photon_ml_tpu.cli.join_feedback",
    "score_game": "photon_ml_tpu.cli.score_game",
    "serve_game": "photon_ml_tpu.cli.serve_game",
    "serve_fleet": "photon_ml_tpu.cli.serve_fleet",
    "build_index": "photon_ml_tpu.cli.build_index",
}


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    if not argv or argv[0] in ("-h", "--help") or argv[0] not in _DRIVERS:
        names = ", ".join(_DRIVERS)
        print(f"usage: python -m photon_ml_tpu {{{names}}} [options]\n"
              f"run a driver with -h for its options")
        raise SystemExit(0 if argv and argv[0] in ("-h", "--help") else 2)
    import importlib

    from photon_ml_tpu import compile_cache

    compile_cache.configure()
    driver = importlib.import_module(_DRIVERS[argv[0]])
    result = driver.run(argv[1:])
    if result:
        print(result)


if __name__ == "__main__":
    main()
