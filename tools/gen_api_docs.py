"""Generate docs/api/<subpackage>.md from the live package.

The reference ships a full mkdocs-autodoc API site; this box can't install
mkdocstrings, so this script is the equivalent: it imports every
``fl4health_tpu`` module, collects the public symbols each module DEFINES
(not re-exports), and writes one markdown page per subpackage with
signatures and the first docstring paragraph — which in this codebase
carries the reference-parity citation. Regenerate after API changes:

    python tools/gen_api_docs.py        # rewrites docs/api/*.md

Runs on the CPU backend: a doc build needs no device.
"""

from __future__ import annotations

import importlib
import inspect
import os
import pkgutil
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT_DIR = os.path.join(REPO, "docs", "api")

import fl4health_tpu  # noqa: E402


def first_paragraph(obj) -> str:
    doc = inspect.getdoc(obj)
    if not doc:
        return ""
    head = doc.split("\n\n")[0].replace("\n", " ").strip()
    # flax dataclass auto-docstrings embed sentinel reprs with addresses
    return re.sub(r" at 0x[0-9a-f]+", "", head)


def signature_of(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # default values that repr as "<... object at 0x...>" (flax sentinels)
    # would churn the generated files with fresh addresses on every run
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def public_symbols(mod):
    """Symbols defined in (not imported into) the module, docstring order."""
    out = []
    for name, obj in vars(mod).items():
        if name.startswith("_"):
            continue
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if getattr(obj, "__module__", None) != mod.__name__:
            continue
        try:
            line = inspect.getsourcelines(obj)[1]
        except (OSError, TypeError):
            line = 1 << 30
        out.append((line, name, obj))
    return [(n, o) for _, n, o in sorted(out)]


def class_methods(cls):
    out = []
    for name, obj in vars(cls).items():
        if name.startswith("_") or not inspect.isfunction(obj):
            continue
        try:
            line = inspect.getsourcelines(obj)[1]
        except (OSError, TypeError):
            line = 1 << 30
        out.append((line, name, obj))
    return [(n, o) for _, n, o in sorted(out)]


def render_module(mod) -> list[str]:
    lines: list[str] = []
    syms = public_symbols(mod)
    if not syms:
        return lines
    lines.append(f"## `{mod.__name__}`\n")
    head = first_paragraph(mod)
    if head:
        lines.append(head + "\n")
    for name, obj in syms:
        kind = "class" if inspect.isclass(obj) else "def"
        lines.append(f"### `{kind} {name}{signature_of(obj)}`\n")
        doc = first_paragraph(obj)
        if doc:
            lines.append(doc + "\n")
        if inspect.isclass(obj):
            for mname, mobj in class_methods(obj):
                mdoc = first_paragraph(mobj)
                lines.append(
                    f"- `.{mname}{signature_of(mobj)}`"
                    + (f" — {mdoc}" if mdoc else "")
                )
            if class_methods(obj):
                lines.append("")
    return lines


def main() -> int:
    os.makedirs(OUT_DIR, exist_ok=True)
    pages: dict[str, list[str]] = {}
    pkg_path = fl4health_tpu.__path__
    for info in sorted(pkgutil.walk_packages(pkg_path, "fl4health_tpu."),
                       key=lambda i: i.name):
        if info.ispkg:
            continue
        if any(p.startswith("_") for p in info.name.split(".")):
            continue  # private modules / native extensions (_codec)
        try:
            mod = importlib.import_module(info.name)
        except ImportError as e:
            print(f"skip {info.name}: {e}", file=sys.stderr)
            continue
        parts = info.name.split(".")
        page = parts[1] if len(parts) > 2 else "top_level"
        pages.setdefault(page, []).extend(render_module(mod))

    index = [
        "# API reference\n",
        "Generated by `tools/gen_api_docs.py` from the live package — each",
        "docstring's first paragraph carries its reference-parity citation",
        "(`/root/reference/fl4health/<file>:<line>`). One page per",
        "subpackage:\n",
    ]
    for page in sorted(pages):
        body = pages[page]
        if not body:
            continue
        with open(os.path.join(OUT_DIR, f"{page}.md"), "w") as f:
            f.write(f"# `fl4health_tpu.{page}`\n\n" if page != "top_level"
                    else "# Top-level modules\n\n")
            f.write("\n".join(body).rstrip() + "\n")
        index.append(f"- [{page}](api/{page}.md)")
    with open(os.path.join(REPO, "docs", "api_index.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print(f"wrote {len(pages)} pages to docs/api/ + docs/api_index.md")
    # every generated page must be registered in the mkdocs nav — a new
    # subpackage (e.g. observability) that lands here but not in mkdocs.yml
    # would build a site with an orphaned page
    with open(os.path.join(REPO, "mkdocs.yml")) as f:
        nav = f.read()
    missing = [p for p in sorted(pages) if pages[p] and f"api/{p}.md" not in nav]
    if missing:
        print(f"WARNING: pages missing from mkdocs.yml nav: {missing}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
