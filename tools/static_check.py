"""Static consistency gate (the reference runs mypy, Makefile:20;
mypy is not installable in this zero-egress image, so this is the
stdlib equivalent): byte-compile every source file, then import every
module of the package under a scrubbed CPU backend — catching syntax
errors, missing imports, and module-level typos across the whole tree
in one pass.

Also a fault-injection seam lint (ISSUE 19): every socket-touching
call in ``pydcop_tpu/serving/`` must route through
``serving/netfault.py`` — raw ``http.client``/``urllib``/``socket``
use in the serve plane would silently bypass the injectable link
faults the chaos gate relies on, making partition scenarios prove
nothing about the code path production runs.

Run:  python tools/static_check.py      (exit 0 = clean)
"""

import compileall
import importlib
import os
import pkgutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Tokens that open sockets directly.  serving/netfault.py is the one
# allowed user (it IS the seam); serving/http.py and telemetry.py are
# SERVER-side (socketserver binds, no outbound links to fault), so
# only outbound-client tokens are banned there.
_SOCKET_TOKENS = (
    "http.client",
    "HTTPConnection(",
    "urllib.request",
    "urlopen(",
    "socket.create_connection",
)
_SEAM_ALLOWLIST = ("netfault.py",)


def check_netfault_seam() -> int:
    serving = os.path.join(REPO, "pydcop_tpu", "serving")
    bad = []
    for fname in sorted(os.listdir(serving)):
        if not fname.endswith(".py") or fname in _SEAM_ALLOWLIST:
            continue
        path = os.path.join(serving, fname)
        with open(path, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                code = line.split("#", 1)[0]
                for tok in _SOCKET_TOKENS:
                    if tok in code:
                        bad.append((fname, lineno, tok,
                                    line.strip()))
    if bad:
        print("static_check: raw socket I/O in the serve plane must "
              "route through serving/netfault.py (the fault-"
              "injection seam):")
        for fname, lineno, tok, line in bad:
            print(f"  pydcop_tpu/serving/{fname}:{lineno}: "
                  f"{tok!r} in: {line}")
        return 1
    return 0


def _call_sites(src: str, needle: str):
    """Yield (lineno, full_call_text) for every ``needle(`` call in
    ``src`` with balanced-paren capture (calls span lines).  ``def
    needle(`` definitions are skipped — the lint is about callers."""
    lines = src.splitlines()
    i = 0
    while i < len(lines):
        code = lines[i].split("#", 1)[0]
        col = code.find(needle + "(")
        if col < 0 or code.lstrip().startswith("def "):
            i += 1
            continue
        depth, j, text = 0, i, []
        pos = col + len(needle)
        while j < len(lines):
            chunk = lines[j].split("#", 1)[0]
            seg = chunk[pos:] if j == i else chunk
            for ch in seg:
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
            text.append(seg)
            if depth <= 0 and j >= i:
                break
            pos = 0
            j += 1
        yield i + 1, "\n".join(text)
        i = j + 1


def check_trace_seam() -> int:
    """Fleet-trace context seam (ISSUE 20): every router-side
    ``_forward(``/``open_stream(`` call site must DECIDE about trace
    context explicitly — ``trace=`` (``headers=`` for streams), even
    if the decision is ``trace=None`` (telemetry-plane probes).  A
    forward without the kwarg is a causal-tree hole: the replica
    would mint a fresh trace_id and the hop vanishes from
    ``/fleet/forensics``."""
    bad = []
    for fname in ("router.py", "migration.py"):
        path = os.path.join(REPO, "pydcop_tpu", "serving", fname)
        with open(path, encoding="utf-8") as f:
            src = f.read()
        for lineno, call in _call_sites(src, "_forward"):
            if "trace=" not in call:
                bad.append((fname, lineno, "_forward", "trace="))
        for lineno, call in _call_sites(src, "open_stream"):
            if "headers=" not in call:
                bad.append((fname, lineno, "open_stream", "headers="))
    if bad:
        print("static_check: router forwarding call sites must "
              "attach trace context explicitly (trace=ctx, or "
              "trace=None for telemetry-plane probes) — see "
              "docs/observability.md \"Fleet tracing\":")
        for fname, lineno, fn, kwarg in bad:
            print(f"  pydcop_tpu/serving/{fname}:{lineno}: "
                  f"{fn}(...) without {kwarg}")
        return 1
    return 0


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)

    ok = compileall.compile_dir(
        os.path.join(REPO, "pydcop_tpu"), quiet=1, force=True)
    ok &= compileall.compile_dir(
        os.path.join(REPO, "tests"), quiet=1, force=True)
    if not ok:
        print("static_check: byte-compilation failed")
        return 1

    if check_netfault_seam():
        return 1

    if check_trace_seam():
        return 1

    import pydcop_tpu

    failures = []
    for mod in pkgutil.walk_packages(
            pydcop_tpu.__path__, prefix="pydcop_tpu."):
        try:
            importlib.import_module(mod.name)
        except Exception as exc:  # noqa: BLE001 - report, don't mask
            failures.append((mod.name, f"{type(exc).__name__}: {exc}"))
    if failures:
        print(f"static_check: {len(failures)} module(s) failed to "
              "import:")
        for name, err in failures:
            print(f"  {name}: {err}")
        return 1
    print("static_check: all modules compile and import cleanly")
    return 0


if __name__ == "__main__":
    sys.exit(main())
