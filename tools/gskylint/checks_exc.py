"""GSKY-EXC: silent swallows and the device-error hierarchy.

Two rules:

X1  an ``except Exception:`` / ``except BaseException:`` / bare
    ``except:`` handler whose body is only ``pass``/``continue``
    must carry a comment (on the ``except`` line or inside the body)
    saying *why* swallowing is correct — telemetry-must-never-break-
    serving is a real idiom in this tree, but an unannotated swallow
    is indistinguishable from a bug, and on server/worker paths it
    eats the very errors the 503 mapping and the device supervisor
    classify.  Bare ``except:`` additionally catches
    ``KeyboardInterrupt``/``SystemExit`` and is flagged even when
    commented.

X2  exception classes defined under ``gsky_tpu/device_guard/`` must
    stay inside the ``DeviceGuardError ⊂ BackendUnavailable``
    hierarchy (subclass one of the two, directly) — a device error
    outside it would dodge the gateway's 503+Retry-After mapping and
    surface as a bare 500.
"""

from __future__ import annotations

import ast
from typing import List

from .engine import Finding, RepoContext

CODE = "GSKY-EXC"
_BROAD = {"Exception", "BaseException"}
_HIERARCHY_BASES = {"DeviceGuardError", "BackendUnavailable"}


def _handler_types(handler: ast.ExceptHandler) -> List[str]:
    t = handler.type
    if t is None:
        return []
    nodes = t.elts if isinstance(t, ast.Tuple) else [t]
    names = []
    for n in nodes:
        if isinstance(n, ast.Name):
            names.append(n.id)
        elif isinstance(n, ast.Attribute):
            names.append(n.attr)
    return names


def _body_is_swallow(handler: ast.ExceptHandler) -> bool:
    return all(isinstance(s, (ast.Pass, ast.Continue))
               for s in handler.body)


def _has_comment(sf, start: int, end: int) -> bool:
    for ln in range(start, end + 1):
        if "#" in sf.line_text(ln):
            return True
    return False


def check(ctx: RepoContext) -> List[Finding]:
    out: List[Finding] = []
    for sf in ctx.files:
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ExceptHandler):
                names = _handler_types(node)
                broad = node.type is None or \
                    any(n in _BROAD for n in names)
                if not broad or not _body_is_swallow(node):
                    continue
                last = node.body[-1]
                end = getattr(last, "end_lineno", last.lineno)
                if node.type is None:
                    out.append(Finding(
                        CODE, sf.path, node.lineno,
                        "bare `except:` swallow also traps "
                        "KeyboardInterrupt/SystemExit (X1) — catch "
                        "Exception at most"))
                elif not _has_comment(sf, node.lineno, end):
                    out.append(Finding(
                        CODE, sf.path, node.lineno,
                        "unannotated `except Exception: pass` (X1) — "
                        "say why swallowing is safe in a comment, or "
                        "handle/log the error"))
            elif isinstance(node, ast.ClassDef) and \
                    sf.path.startswith("gsky_tpu/device_guard/"):
                names = set()
                for b in node.bases:
                    if isinstance(b, ast.Name):
                        names.add(b.id)
                    elif isinstance(b, ast.Attribute):
                        names.add(b.attr)
                looks_exc = node.name.endswith(("Error", "Fault")) or \
                    any(n.endswith(("Error", "Exception")) or
                        n in _HIERARCHY_BASES for n in names)
                if looks_exc and not (names & _HIERARCHY_BASES):
                    out.append(Finding(
                        CODE, sf.path, node.lineno,
                        f"device exception {node.name} is outside the "
                        f"DeviceGuardError ⊂ BackendUnavailable "
                        f"hierarchy (X2) — it would bypass the "
                        f"gateway's 503 mapping"))
    return out
