"""``pydcop`` command-line interface.

Reference parity: pydcop/dcop_cli.py (:62-130) — subcommands solve, run,
distribute, graph, agent, orchestrator, generate, replica_dist, batch,
consolidate; global ``--timeout``, ``--output``, verbosity flags.
"""

import argparse
import logging
import sys


def _configure_logs(level: int):
    if level >= 3:
        log_level = logging.DEBUG
    elif level == 2:
        log_level = logging.INFO
    elif level == 1:
        log_level = logging.WARNING
    else:
        log_level = logging.ERROR
    logging.basicConfig(
        level=log_level,
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
        stream=sys.stderr,
    )


def make_parser() -> argparse.ArgumentParser:
    from pydcop_tpu.commands import (
        agent,
        batch,
        consolidate,
        debug,
        distribute,
        fleet,
        generate,
        graph,
        orchestrator,
        profile,
        replica_dist,
        run,
        serve,
        solve,
        trace,
    )

    parser = argparse.ArgumentParser(
        prog="pydcop",
        description="TPU-native DCOP solver with pyDCOP capabilities",
    )
    parser.add_argument(
        "-t", "--timeout", type=float, default=None,
        help="global timeout in seconds",
    )
    parser.add_argument(
        "--output", default=None, help="output file for results"
    )
    parser.add_argument(
        "-v", "--verbosity", type=int, default=0,
        help="verbosity: 0 error, 1 warning, 2 info, 3 debug",
    )
    parser.add_argument(
        "--version", action="store_true", help="print version and exit"
    )
    subparsers = parser.add_subparsers(title="commands", dest="command")
    for cmd in (solve, run, distribute, graph, agent, orchestrator,
                generate, replica_dist, batch, consolidate, trace,
                serve, debug, profile, fleet):
        cmd.set_parser(subparsers)
    return parser


def cli(args=None):
    """Console-script entry point (NOT for in-process use).

    Agent-mode runs leave daemon threads behind (agents, HTTP servers,
    websocket servers, JAX clients); interpreter teardown can race them
    into an abort after the result is already printed.  Flush and exit
    hard — all user-visible work is done.  Programmatic callers should
    use :func:`main`, which returns normally.
    """
    rc = main(args)
    sys.stdout.flush()
    sys.stderr.flush()
    import os
    import threading

    if any(
        t.daemon and t.is_alive() and t is not threading.main_thread()
        for t in threading.enumerate()
    ):
        os._exit(rc)
    sys.exit(rc)


def main(args=None) -> int:
    parser = make_parser()
    parsed = parser.parse_args(args)
    _configure_logs(parsed.verbosity)
    if parsed.version:
        import pydcop_tpu
        from pydcop_tpu.dcop.yamldcop import YAML_LOADER

        # "python" means PyYAML was built without libyaml: loading a
        # problem takes several times as long.
        print(f"pydcop-tpu {pydcop_tpu.__version__} "
              f"(yaml loader: {YAML_LOADER})")
        return 0
    if not getattr(parsed, "func", None):
        parser.print_help()
        return 2
    try:
        return parsed.func(parsed) or 0
    except ModuleNotFoundError as e:
        # Plugin-style lookups (algorithm / distribution / graph model
        # names map to module imports): name the valid options.  NOTE:
        # a bare `raise` here would escape the whole try statement
        # (later handlers never apply once one is entered), so the
        # generic path is handled inline.
        name = str(e).rsplit(".", 1)[-1].rstrip("'")
        if "pydcop_tpu.algorithms." in str(e):
            from pydcop_tpu.algorithms import list_available_algorithms

            print(
                f"Error: unknown algorithm {name!r}; available: "
                f"{', '.join(list_available_algorithms())}",
                file=sys.stderr,
            )
            return 2
        if "pydcop_tpu.distribution." in str(e):
            print(
                f"Error: unknown distribution method {name!r}",
                file=sys.stderr,
            )
            return 2
        if "pydcop_tpu.computations_graph." in str(e):
            import pkgutil

            import pydcop_tpu.computations_graph as cg_pkg

            models = sorted(
                n for _, n, ispkg in pkgutil.iter_modules(cg_pkg.__path__)
                if not ispkg and not n.startswith("_") and n != "objects"
            )
            print(
                f"Error: unknown graph model {name!r}; available: "
                f"{', '.join(models)}",
                file=sys.stderr,
            )
            return 2
        if parsed.verbosity >= 3:
            raise
        if "pydcop_tpu" not in str(e):
            # A missing THIRD-PARTY module is a broken install, not a
            # user error (ADVICE r2): distinct exit code + -vvv hint.
            print(
                f"Error: missing dependency: {e}. This looks like a "
                "broken installation; rerun with -vvv for the full "
                "traceback.",
                file=sys.stderr,
            )
            return 3
        print(f"Error: {e}", file=sys.stderr)
        return 1
    except FileNotFoundError as e:
        print(f"Error: file not found: {e.filename}", file=sys.stderr)
        return 2
    except Exception as e:  # clean one-line errors for users, not tracebacks
        if parsed.verbosity >= 3:
            raise
        print(f"Error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    cli()
