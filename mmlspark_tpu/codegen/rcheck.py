"""Vendored R syntax checker for the generated bindings.

No R runtime exists in this build environment (review round 3 Weak #7), so
the generated package cannot be smoke-loaded; this module pins the next
best guarantee: every generated ``.R`` file passes a real lexical parse
— string- and comment-aware delimiter matching, function-definition
argument grammar, and roxygen tag validity — instead of the previous
brace-counting heuristic (which a brace inside a string literal or
comment would both fool).

Scope: the R subset the generator emits (``rgen.py``) — function
definitions, calls, ``list()``, ``if``, ``$`` access, strings,
``NULL`` defaults, roxygen comments. It is a validator for OUR
templates, not a general R parser.
"""

from __future__ import annotations

import re

_OPENERS = {"(": ")", "{": "}", "[": "]"}
_CLOSERS = {v: k for k, v in _OPENERS.items()}
_ROXYGEN_TAGS = {"param", "export", "return", "title", "description"}
_IDENT = re.compile(r"^[a-zA-Z.][a-zA-Z0-9._]*$")


class RSyntaxError(ValueError):
    def __init__(self, path: str, line: int, message: str):
        super().__init__(f"{path}:{line}: {message}")
        self.path, self.line, self.message = path, line, message


def _lex(text: str, path: str) -> list[tuple[str, int]]:
    """Strip comments and collapse string literals (string- and
    escape-aware), returning (delimiter-or-code char, line) events for
    the matcher. Raises on an unterminated string."""
    events: list[tuple[str, int]] = []
    line = 1
    i, n = 0, len(text)
    while i < n:
        ch = text[i]
        if ch == "\n":
            line += 1
            i += 1
        elif ch == "#":
            while i < n and text[i] != "\n":
                i += 1
        elif ch in ('"', "'"):
            quote, start = ch, line
            i += 1
            while i < n:
                if text[i] == "\\":
                    i += 2
                    continue
                if text[i] == "\n":
                    line += 1
                if text[i] == quote:
                    break
                i += 1
            else:
                raise RSyntaxError(path, start, "unterminated string")
            i += 1
        else:
            if ch in _OPENERS or ch in _CLOSERS:
                events.append((ch, line))
            i += 1
    return events


def _check_delimiters(text: str, path: str) -> None:
    stack: list[tuple[str, int]] = []
    for ch, line in _lex(text, path):
        if ch in _OPENERS:
            stack.append((ch, line))
        else:
            if not stack:
                raise RSyntaxError(path, line, f"unmatched {ch!r}")
            opener, oline = stack.pop()
            if _OPENERS[opener] != ch:
                raise RSyntaxError(
                    path, line,
                    f"mismatched {ch!r} (opened {opener!r} at line "
                    f"{oline})")
    if stack:
        opener, oline = stack[-1]
        raise RSyntaxError(path, oline, f"unclosed {opener!r}")


def _split_args(argstr: str) -> list[str]:
    """Split a definition arg list on top-level commas (string- and
    paren-aware)."""
    out, depth, cur, in_str = [], 0, [], ""
    i = 0
    while i < len(argstr):
        ch = argstr[i]
        if in_str:
            if ch == "\\":
                cur.append(argstr[i:i + 2])
                i += 2
                continue
            if ch == in_str:
                in_str = ""
        elif ch in ('"', "'"):
            in_str = ch
        elif ch in _OPENERS:
            depth += 1
        elif ch in _CLOSERS:
            depth -= 1
        elif ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
            i += 1
            continue
        cur.append(ch)
        i += 1
    if cur:
        out.append("".join(cur))
    return out


_FUNDEF = re.compile(
    r"^([a-zA-Z.][a-zA-Z0-9._]*)\s*<-\s*function\s*\((.*)\)\s*\{\s*$")


def _check_fundefs(text: str, path: str) -> list[str]:
    """Validate every single-line function definition the generator
    emits; returns the defined names."""
    defined = []
    for lineno, line in enumerate(text.splitlines(), 1):
        stripped = line.strip()
        if "<- function" not in stripped or stripped.startswith("#"):
            continue
        m = _FUNDEF.match(stripped)
        if m is None:
            raise RSyntaxError(path, lineno,
                               f"malformed function definition: "
                               f"{stripped[:60]!r}")
        defined.append(m.group(1))
        for arg in _split_args(m.group(2)):
            arg = arg.strip()
            if not arg:
                continue
            name = arg.split("=", 1)[0].strip()
            if not _IDENT.match(name):
                raise RSyntaxError(
                    path, lineno, f"invalid argument name {name!r}")
    return defined


def _check_roxygen(text: str, path: str) -> None:
    for lineno, line in enumerate(text.splitlines(), 1):
        stripped = line.strip()
        if not stripped.startswith("#'"):
            continue
        body = stripped[2:].strip()
        if body.startswith("@"):
            tag = body[1:].split(None, 1)[0]
            if tag not in _ROXYGEN_TAGS:
                raise RSyntaxError(path, lineno,
                                   f"unknown roxygen tag @{tag}")
            if tag == "param" and len(body.split(None, 2)) < 2:
                raise RSyntaxError(path, lineno,
                                   "@param without a name")


def check_r_source(text: str, path: str = "<string>") -> list[str]:
    """Full check of one generated R source; returns defined function
    names."""
    _check_delimiters(text, path)
    _check_roxygen(text, path)
    return _check_fundefs(text, path)


def check_package(out_dir: str) -> dict[str, list[str]]:
    """Validate a generated package tree (every R/*.R + NAMESPACE
    export coverage). Returns {file: defined function names}."""
    import os
    r_dir = os.path.join(out_dir, "R")
    result: dict[str, list[str]] = {}
    defined: set[str] = set()
    for name in sorted(os.listdir(r_dir)):
        if not name.endswith(".R"):
            continue
        path = os.path.join(r_dir, name)
        with open(path) as f:
            fns = check_r_source(f.read(), path)
        result[name] = fns
        defined.update(fns)
    ns_path = os.path.join(out_dir, "NAMESPACE")
    with open(ns_path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            m = re.fullmatch(r"export\(([a-zA-Z.][a-zA-Z0-9._]*)\)", line)
            if m is None:
                raise RSyntaxError(ns_path, lineno,
                                   f"malformed NAMESPACE line {line!r}")
            if m.group(1) not in defined:
                raise RSyntaxError(
                    ns_path, lineno,
                    f"export({m.group(1)}) has no definition")
    return result
