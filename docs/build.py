"""Static API-reference builder (pdoc-style, zero extra dependencies).

``python -m docs.build [--out DIR] [--no-strict]`` walks the public API
surface (the curated module list below — ``repro.core``, ``repro.stream``,
``repro.serve``, ``repro.kernels``), extracts signatures and docstrings
with ``inspect``, and renders one static HTML page per module plus an
index. Docstrings render as Markdown when the ``markdown`` package is
available, as preformatted text otherwise.

The build **fails** (exit 1, default strict mode) when any warning fires:

* a listed module is missing or has no module docstring,
* a public symbol (function, class, public method/property defined in the
  module) has no docstring,
* a signature cannot be resolved.

That makes the CI docs job a docstring-coverage gate for every module on
the list — growing the public surface means documenting it.
"""
from __future__ import annotations

import argparse
import html
import importlib
import inspect
import sys

#: The public API surface. Order is the index order.
MODULES: tuple[str, ...] = (
    "repro.api",
    "repro.core.slsh",
    "repro.core.pipeline",
    "repro.core.routing",
    "repro.core.distributed",
    "repro.core.hashing",
    "repro.core.tables",
    "repro.core.topk",
    "repro.core.pknn",
    "repro.core.predict",
    "repro.core.merge",
    "repro.runtime.memory",
    "repro.runtime.payload",
    "repro.runtime.elastic",
    "repro.data.windows",
    "repro.obs",
    "repro.obs.trace",
    "repro.obs.metrics",
    "repro.obs.clock",
    "repro.stream.index",
    "repro.stream.delta",
    "repro.stream.shard",
    "repro.stream.monitor",
    "repro.serve.engine",
    "repro.serve.frontend",
    "repro.serve.coalesce",
    "repro.serve.admission",
    "repro.launch.mesh",
    "repro.kernels.blocking",
    "repro.kernels.hash_pack.ops",
    "repro.kernels.inner_probe.ops",
    "repro.kernels.l1_topk.ops",
    "repro.kernels.query_fused.ops",
    "repro.kernels.flash_attention.ops",
)

_CSS = """
body { font-family: -apple-system, 'Segoe UI', sans-serif; margin: 0 auto;
       max-width: 60rem; padding: 1rem 2rem; color: #1a1a1a; }
h1 { border-bottom: 2px solid #ddd; padding-bottom: .3rem; }
h2.symbol { font-family: ui-monospace, monospace; font-size: 1.05rem;
            background: #f4f4f6; padding: .4rem .6rem; border-radius: 4px; }
pre, code { background: #f4f4f6; border-radius: 3px; }
pre { padding: .6rem; overflow-x: auto; }
.kind { color: #888; font-size: .8rem; text-transform: uppercase;
        letter-spacing: .05em; }
.member { margin-left: 1.5rem; }
nav a { margin-right: 1rem; }
footer { margin-top: 3rem; color: #999; font-size: .85rem; }
"""


def _render_doc(doc: str) -> str:
    """Docstring -> HTML (Markdown when available, escaped <pre> fallback)."""
    doc = inspect.cleandoc(doc)
    try:
        import markdown

        return markdown.markdown(doc, extensions=["fenced_code", "tables"])
    except ImportError:
        return f"<pre>{html.escape(doc)}</pre>"


def _signature(obj) -> str | None:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return None


def _public_members(mod):
    """Public symbols *defined in* ``mod`` (re-exports documented at home)."""
    for name, obj in sorted(vars(mod).items()):
        if name.startswith("_"):
            continue
        if not (inspect.isfunction(obj) or inspect.isclass(obj)):
            continue
        if getattr(obj, "__module__", None) != mod.__name__:
            continue
        yield name, obj


def _class_members(cls):
    """Public methods/properties declared on the class itself."""
    for name, obj in sorted(vars(cls).items()):
        if name.startswith("_"):
            continue
        if callable(obj) or isinstance(obj, property):
            yield name, obj


def document_module(mod_name: str, warn) -> str:
    """Render one module page; emits warnings through ``warn``."""
    try:
        mod = importlib.import_module(mod_name)
    except Exception as e:  # noqa: BLE001
        warn(f"{mod_name}: import failed: {e}")
        return f"<h1>{mod_name}</h1><p>import failed</p>"
    parts = [f"<h1><code>{mod_name}</code></h1>"]
    if not mod.__doc__:
        warn(f"{mod_name}: missing module docstring")
    else:
        parts.append(_render_doc(mod.__doc__))
    for name, obj in _public_members(mod):
        kind = "class" if inspect.isclass(obj) else "function"
        sig = _signature(obj)
        if sig is None and not inspect.isclass(obj):
            warn(f"{mod_name}.{name}: unresolvable signature")
            sig = "(...)"
        shown = f"{name}{sig or ''}"
        parts.append(f'<h2 class="symbol" id="{name}">{html.escape(shown)}</h2>')
        parts.append(f'<div class="kind">{kind}</div>')
        doc = inspect.getdoc(obj)
        if not doc:
            warn(f"{mod_name}.{name}: missing docstring")
        else:
            parts.append(_render_doc(doc))
        if inspect.isclass(obj):
            fields = getattr(obj, "__annotations__", {})
            if fields:
                rows = "".join(
                    f"<li><code>{html.escape(f)}</code>: "
                    f"<code>{html.escape(str(t))}</code></li>"
                    for f, t in fields.items()
                )
                parts.append(f'<div class="member"><ul>{rows}</ul></div>')
            for mname, mobj in _class_members(obj):
                target = mobj.fget if isinstance(mobj, property) else mobj
                msig = _signature(target) if callable(target) else ""
                parts.append(
                    f'<div class="member"><h3><code>'
                    f"{html.escape(f'{name}.{mname}{msig or ()}')}"
                    f"</code></h3>"
                )
                mdoc = inspect.getdoc(mobj)
                if not mdoc:
                    warn(f"{mod_name}.{name}.{mname}: missing docstring")
                    parts.append("</div>")
                else:
                    parts.append(_render_doc(mdoc) + "</div>")
    return "\n".join(parts)


def _page(title: str, body: str, rel_index: str = "index.html") -> str:
    return (
        "<!doctype html><html><head><meta charset='utf-8'>"
        f"<title>{html.escape(title)}</title><style>{_CSS}</style></head>"
        f"<body><nav><a href='{rel_index}'>API index</a>"
        "<a href='operating.html'>Operator guide</a></nav>"
        f"{body}<footer>Generated by <code>python -m docs.build</code>"
        "</footer></body></html>"
    )


def build(out_dir: str, strict: bool = True) -> int:
    """Build the reference into ``out_dir``; returns the exit code."""
    import os

    warnings: list[str] = []
    warn = warnings.append
    os.makedirs(out_dir, exist_ok=True)
    toc = ["<h1>DSLSH API reference</h1><ul>"]
    for mod_name in MODULES:
        body = document_module(mod_name, warn)
        fname = mod_name.replace(".", "_") + ".html"
        with open(os.path.join(out_dir, fname), "w") as f:
            f.write(_page(mod_name, body))
        mod = sys.modules.get(mod_name)
        first = ""
        if mod and mod.__doc__:
            first = html.escape(mod.__doc__.strip().splitlines()[0])
        toc.append(f"<li><a href='{fname}'><code>{mod_name}</code></a> — {first}</li>")
    toc.append("</ul>")
    # operator guide rides along so the built site is self-contained
    guide = os.path.join(os.path.dirname(__file__), "operating.md")
    if os.path.exists(guide):
        with open(guide) as f:
            guide_html = _render_doc(f.read())
        with open(os.path.join(out_dir, "operating.html"), "w") as f:
            f.write(_page("Operator guide", guide_html))
    else:
        warn("docs/operating.md missing")
    with open(os.path.join(out_dir, "index.html"), "w") as f:
        f.write(_page("DSLSH API reference", "\n".join(toc)))
    for w in warnings:
        print(f"docs.build warning: {w}", file=sys.stderr)
    print(f"built {len(MODULES)} module pages -> {out_dir} "
          f"({len(warnings)} warnings)")
    if warnings and strict:
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="docs/_build")
    ap.add_argument(
        "--no-strict", action="store_true",
        help="report warnings without failing the build",
    )
    args = ap.parse_args(argv)
    return build(args.out, strict=not args.no_strict)


if __name__ == "__main__":
    raise SystemExit(main())
