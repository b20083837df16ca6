"""nGQL lexer.

Capability parity with the reference's flex scanner
(/root/reference/src/parser/scanner.lex): case-insensitive keywords,
identifiers, dec/hex int literals, doubles, single/double-quoted strings
with escapes, the full operator set (incl. ``->``, ``|`` vs ``||``,
``$-``/``$^``/``$$``/``$var`` references), line comments (``--``, ``#``,
``//``), block comments (``/* */``, unterminated -> error,
scanner.lex:399-408), and bare IPv4 literals for host lists
(``ADD HOSTS 127.0.0.1:1000``).
"""
from __future__ import annotations

import re
from typing import Iterator, List, NamedTuple, Optional

from ...common.status import Status


class LexError(Exception):
    pass


class Token(NamedTuple):
    type: str       # KW / ID / INT / FLOAT / STRING / SYM / REF / EOF
    value: object
    pos: int


KEYWORDS = {
    "go", "steps", "step", "from", "over", "reversely", "bidirect", "where",
    "yield",
    "distinct", "as", "to", "upto", "match", "find", "path", "shortest",
    "all", "fetch", "prop", "on", "union", "intersect", "minus", "use",
    "show", "spaces", "tags", "edges", "hosts", "parts", "users", "configs",
    "stats", "events", "queries", "timeline", "kill", "query",
    "variables", "add", "remove", "create", "drop", "alter", "describe",
    "desc", "tag", "edge", "space", "if", "not", "exists", "insert",
    "vertex", "values", "update", "upsert", "set", "delete", "order", "by",
    "asc", "change", "int", "double", "string", "bool", "timestamp", "true",
    "false", "user", "password", "with", "grant", "revoke", "role", "roles",
    "god",
    "admin", "guest", "balance", "data", "leader", "stop", "download",
    "hdfs", "ingest", "get", "group", "limit", "offset", "when", "of",
    "graph", "meta", "storage", "uuid", "or", "and", "xor", "no",
    "overwrite", "vertices", "in", "out", "both",
}
# NOTE: PROFILE/EXPLAIN are deliberately NOT keywords — reserving them
# broke bare identifiers named profile/explain in expression position
# (ORDER BY profile).  The parser special-cases the two words only as
# the very first token of a statement list (parser.py parse_sentences),
# where no valid statement can start with a bare identifier.

# comment alternation, shared with the engine's PROFILE-prefix sniff
# (graph/service.py) so the two grammars cannot drift
COMMENT_RE = r"--[^\n]*|\#[^\n]*|//[^\n]*|/\*(?:[^*]|\*(?!/))*\*/"

_TOKEN_RE = re.compile(r"""
    (?P<ws>\s+)
  | (?P<comment>""" + COMMENT_RE + r""")
  | (?P<badcomment>/\*)
  | (?P<ipv4>\d+\.\d+\.\d+\.\d+)
  | (?P<float>\d+\.\d*(?:[eE][-+]?\d+)?|\.\d+(?:[eE][-+]?\d+)?|\d+[eE][-+]?\d+)
  | (?P<int>0[xX][0-9a-fA-F]+|\d+)
  | (?P<string>"(?:\\.|[^"\\])*"|'(?:\\.|[^'\\])*')
  | (?P<ref>\$-|\$\^|\$\$|\$[A-Za-z_][A-Za-z0-9_]*)
  | (?P<id>[A-Za-z_][A-Za-z0-9_]*)
  | (?P<sym>->|\|\||&&|==|!=|<=|>=|[-+*/%!^<>=().,;|@:\[\]{}_])
""", re.VERBOSE)

_ESCAPES = {"n": "\n", "t": "\t", "r": "\r", "\\": "\\", '"': '"',
            "'": "'", "0": "\0", "b": "\b", "f": "\f"}


def _unquote(s: str) -> str:
    body = s[1:-1]
    out = []
    i = 0
    while i < len(body):
        ch = body[i]
        if ch == "\\" and i + 1 < len(body):
            out.append(_ESCAPES.get(body[i + 1], body[i + 1]))
            i += 2
        else:
            out.append(ch)
            i += 1
    return "".join(out)


def tokenize(text: str) -> List[Token]:
    tokens: List[Token] = []
    pos = 0
    n = len(text)
    while pos < n:
        m = _TOKEN_RE.match(text, pos)
        if m is None:
            raise LexError(f"unexpected character {text[pos]!r} near "
                           f"...{text[max(0, pos-10):pos+10]!r}")
        kind = m.lastgroup
        val = m.group()
        if kind == "ws" or kind == "comment":
            pass
        elif kind == "badcomment":
            raise LexError("unterminated comment")    # scanner.lex parity
        elif kind == "ipv4":
            tokens.append(Token("IPV4", val, pos))
        elif kind == "float":
            tokens.append(Token("FLOAT", float(val), pos))
        elif kind == "int":
            tokens.append(Token("INT", int(val, 0), pos))
        elif kind == "string":
            tokens.append(Token("STRING", _unquote(val), pos))
        elif kind == "ref":
            tokens.append(Token("REF", val, pos))
        elif kind == "id":
            low = val.lower()
            if low in KEYWORDS:
                tokens.append(Token("KW", low, pos))
            else:
                tokens.append(Token("ID", val, pos))
        else:
            tokens.append(Token("SYM", val, pos))
        pos = m.end()
    tokens.append(Token("EOF", None, pos))
    return tokens
