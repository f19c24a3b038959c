"""Static consistency check: code-registered metrics vs the docs table,
and every registered instrument against a writer in the package.

Every ``hvd_*`` instrument name registered anywhere in ``horovod_tpu/``
(``counter(...)`` / ``gauge(...)`` / ``histogram(...)`` registry calls
and the KV server's literal ``make_family(...)`` driver gauges) must
appear in docs/observability.md's metric tables, and every ``hvd_*``
name a table documents must be registered in code. The table drifted in
every PR since the metrics plane landed; this pass (wired as a
``tools/premerge.sh`` lane and a tier-1 test) makes the drift a CI
failure that NAMES the missing metrics instead of a docs bug found at
incident time.

Third lane: every instrument bound to a name by a registry call must be
written (``.inc(`` / ``.set(`` / ``.observe(``, directly or through
``.labels(...)``) somewhere in ``horovod_tpu/``. A bare ``.labels(...)``
only materialises a zero cell and is no write; a ``make_family(...)``
literal is built with its samples and needs none. An instrument that
only a script outside the package ever set is measurement nothing in the
product takes: it is named here.

Exit 0 when all three hold; exit 1 listing the mismatch otherwise.
Pure stdlib static analysis — no framework import, no jax.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = os.path.join(REPO, "docs", "observability.md")

#: A registry call (or a literal driver-family construction) whose first
#: argument is the metric name. ``\s*`` spans newlines under re.S so the
#: black-wrapped multi-line forms match too.
_REGISTER_RE = re.compile(
    r"\b(?:counter|gauge|histogram|make_family)\(\s*"
    r"['\"](hvd_[A-Za-z0-9_]+)['\"]", re.S)

#: A registry call bound to a name (``X = counter("hvd_...``, ``self._x =
#: gauge("hvd_...``): the name's last component is what writers call.
_BOUND_RE = re.compile(
    r"([A-Za-z_][A-Za-z0-9_.]*)\s*=\s*(?:[A-Za-z_.]*\.)?"
    r"(?:counter|gauge|histogram)\(\s*['\"](hvd_[A-Za-z0-9_]+)['\"]", re.S)

#: What follows an instrument's name at a write: the method itself, or
#: ``.labels(...)`` (one level of nested parentheses) and then the method.
_WRITE_TAIL = (r"(?:\s*\.labels\([^()]*(?:\([^()]*\)[^()]*)*\))?"
               r"\s*\.(?:inc|set|observe)\(")

#: A metric-table row: a pipe-table line whose first cell is a
#: backticked hvd_* name (labels like ``{phase}`` may trail the name).
_TABLE_ROW_RE = re.compile(r"^\|\s*`(hvd_[A-Za-z0-9_]+)")


def _package_texts(root: str) -> dict[str, str]:
    """{path relative to ``root``: text} of every horovod_tpu/**/*.py."""
    out: dict[str, str] = {}
    pkg = os.path.join(root, "horovod_tpu")
    for dirpath, _dirnames, filenames in os.walk(pkg):
        if "__pycache__" in dirpath:
            continue
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out


def code_metrics(root: str = REPO) -> dict[str, list[str]]:
    """{metric name: [files registering it]} over horovod_tpu/*.py."""
    out: dict[str, list[str]] = {}
    for rel, text in _package_texts(root).items():
        for name in _REGISTER_RE.findall(text):
            out.setdefault(name, []).append(rel)
    return out


def unwritten_metrics(root: str = REPO) -> dict[str, str]:
    """{metric name: "BINDING (file)"} of the instruments a registry call
    binds to a name that nothing in horovod_tpu/ ever writes."""
    texts = _package_texts(root)
    package = "\n".join(texts.values())
    out: dict[str, str] = {}
    for rel, text in texts.items():
        for binding, name in _BOUND_RE.findall(text):
            attr = binding.rsplit(".", 1)[-1]
            if not re.search(r"\b" + re.escape(attr) + _WRITE_TAIL,
                             package, re.S):
                out[name] = f"{binding} ({rel})"
    return out


def doc_metrics(path: str = DOCS) -> set[str]:
    """hvd_* names documented in observability.md's metric tables."""
    out: set[str] = set()
    with open(path, encoding="utf-8") as f:
        for line in f:
            m = _TABLE_ROW_RE.match(line.strip())
            if m:
                out.add(m.group(1))
    return out


def main() -> int:
    registered = code_metrics()
    documented = doc_metrics()
    undocumented = sorted(set(registered) - documented)
    unregistered = sorted(documented - set(registered))
    unwritten = unwritten_metrics()
    if not undocumented and not unregistered and not unwritten:
        print(f"check_metric_docs: ok ({len(registered)} registered "
              f"instruments all tabulated in docs/observability.md, and "
              f"each written inside horovod_tpu/)")
        return 0
    if undocumented:
        print("check_metric_docs: registered in code but MISSING from "
              "docs/observability.md's metric tables:", file=sys.stderr)
        for name in undocumented:
            print(f"  {name}  (registered in "
                  f"{', '.join(sorted(set(registered[name])))})",
                  file=sys.stderr)
    if unregistered:
        print("check_metric_docs: documented in the metric tables but "
              "registered NOWHERE in horovod_tpu/:", file=sys.stderr)
        for name in unregistered:
            print(f"  {name}", file=sys.stderr)
    if unwritten:
        print("check_metric_docs: registered but written NOWHERE in "
              "horovod_tpu/ (no .inc( / .set( / .observe( on it):",
              file=sys.stderr)
        for name, where in sorted(unwritten.items()):
            print(f"  {name}  (bound to {where})", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
