"""Console — interactive nGQL REPL over GraphClient.

Capability parity with the reference console (CliManager.h:16-26,
CmdProcessor.cpp:186-339): readline editing + keyword completion, ASCII
table rendering with per-column width and latency footer, client-side
commands (``exit``/``quit``, ``:batch <file>`` — reference ``batch``),
multi-statement input, and ``--eval`` one-shot mode.

Run: ``python -m nebula_tpu.console.repl --addr 127.0.0.1:43699``
"""
from __future__ import annotations

import argparse
import sys
from typing import List, Optional

from ..clients.graph_client import ExecutionResponse, GraphClient
from ..interface.common import HostAddr

KEYWORDS = [
    "GO", "FROM", "OVER", "REVERSELY", "BIDIRECT", "WHERE", "YIELD", "AS",
    "STEPS",
    "UPTO", "USE", "CREATE", "SPACE", "TAG", "EDGE", "DROP", "ALTER",
    "DESCRIBE", "DESC", "SHOW", "SPACES", "TAGS", "EDGES", "HOSTS",
    "INSERT", "VERTEX", "VALUES", "UPDATE", "DELETE", "FETCH", "PROP",
    "ON", "ORDER", "BY", "ASC", "DESC", "LIMIT", "OFFSET", "GROUP",
    "DISTINCT", "UNION", "INTERSECT", "MINUS", "FIND", "PATH", "SHORTEST",
    "ALL", "MATCH", "SET", "ADD", "REMOVE", "BALANCE", "DATA", "LEADER",
    "CONFIGS", "GET", "USER", "USERS", "GRANT", "REVOKE", "ROLE", "TO",
    "CHANGE", "PASSWORD", "WITH", "TTL_COL", "TTL_DURATION", "INGEST",
    "DOWNLOAD", "HDFS", "PIPE", "VARIABLES", "PROFILE", "EXPLAIN",
    "STATS", "EVENTS",
]


def render_table(resp: ExecutionResponse) -> str:
    """ASCII table identical in spirit to the reference's printResult."""
    cols = resp.column_names or []
    rows = resp.rows or []
    if not cols:
        return "Execution succeeded (no result)"
    cells = [[_fmt(v) for v in row] for row in rows]
    widths = [len(c) for c in cols]
    for row in cells:
        for i, cell in enumerate(row):
            if i < len(widths):
                widths[i] = max(widths[i], len(cell))
    sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
    out = [sep,
           "|" + "|".join(f" {c.ljust(w)} " for c, w in zip(cols, widths))
           + "|", sep]
    for row in cells:
        out.append("|" + "|".join(
            f" {cell.ljust(w)} " for cell, w in zip(row, widths)) + "|")
    out.append(sep)
    out.append(f"Got {len(rows)} rows (server latency "
               f"{resp.latency_in_us} us)")
    return "\n".join(out)


def _fmt(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def render_profile(tree: dict) -> str:
    """Indented span tree for a PROFILE statement (CmdProcessor-style
    plain text): one line per span — name, duration, selected tags."""
    lines = [f"PROFILE (trace {tree.get('trace_id', '?')})"]

    def walk(node: dict, depth: int) -> None:
        tags = node.get("tags") or {}
        tag_str = " ".join(f"{k}={_fmt(v)}" for k, v in sorted(tags.items()))
        lines.append(f"{'  ' * depth}+ {node['name']} "
                     f"{node.get('duration_us', 0)}us"
                     + (f"  [{tag_str}]" if tag_str else ""))
        for child in node.get("children", ()):
            walk(child, depth + 1)

    for root in tree.get("roots", ()):
        walk(root, 1)
    return "\n".join(lines)


class Console:
    def __init__(self, addr: HostAddr, username: str = "user",
                 password: str = "password", client_manager=None):
        self.client = GraphClient(addr, client_manager=client_manager)
        st = self.client.connect(username, password)
        if not st.ok():
            raise RuntimeError(f"connect to {addr} failed: {st}")
        self.space = ""

    # ------------------------------------------------------- commands
    def run_statement(self, stmt: str, out=sys.stdout) -> bool:
        stmt = stmt.strip()
        if not stmt:
            return True
        low = stmt.lower().rstrip(";")
        if low in ("exit", "quit"):
            return False
        if low.startswith(":batch"):
            parts = stmt.split(None, 1)
            if len(parts) < 2:
                print("[ERROR]: usage: :batch <file>", file=out)
                return True
            path = parts[1].rstrip(";")
            try:
                with open(path) as f:
                    lines = f.readlines()
            except OSError as e:
                print(f"[ERROR]: {e}", file=out)
                return True
            for line in lines:
                if line.strip() and not line.strip().startswith("#"):
                    self.run_statement(line, out=out)
            return True
        resp = self.client.execute(stmt)
        if resp.ok():
            if stmt.upper().startswith("USE "):
                self.space = stmt.split(None, 1)[1].rstrip(";")
            print(render_table(resp), file=out)
            if resp.profile:
                print(render_profile(resp.profile), file=out)
        else:
            print(f"[ERROR ({int(resp.error_code)})]: {resp.error_msg}",
                  file=out)
        return True

    def interact(self) -> None:
        try:
            import readline

            def complete(text, state):
                opts = [k for k in KEYWORDS
                        if k.startswith(text.upper())]
                return (opts[state] + " ") if state < len(opts) else None

            readline.set_completer(complete)
            readline.parse_and_bind("tab: complete")
        except ImportError:
            pass
        print("Welcome to nebula-tpu console!")
        while True:
            try:
                prompt = f"(user@nebula-tpu) [{self.space}]> "
                line = input(prompt)
            except (EOFError, KeyboardInterrupt):
                print()
                break
            if not self.run_statement(line):
                break
        self.client.disconnect()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="nebula-console")
    p.add_argument("--addr", default="127.0.0.1:43699")
    p.add_argument("-u", "--user", default="user")
    p.add_argument("-p", "--password", default="password")
    p.add_argument("-e", "--eval", default=None,
                   help="run one statement and exit")
    p.add_argument("-f", "--file", default=None,
                   help="run statements from file and exit (batch)")
    args = p.parse_args(argv)
    con = Console(HostAddr.parse(args.addr), args.user, args.password)
    if args.eval:
        con.run_statement(args.eval)
        return 0
    if args.file:
        con.run_statement(f":batch {args.file}")
        return 0
    con.interact()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
