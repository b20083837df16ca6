"""nGQL recursive-descent parser.

Capability parity with the reference's bison grammar
(/root/reference/src/parser/parser.yy — go_sentence:431, match:561,
find:565, fetch:676, use:681, traverse:883, set:893, piped:922,
mutate:1486, maintain:1497, sentences:1537) re-founded as a hand-written
recursive-descent parser (no generator needed; the grammar is LL(2)-ish
with small lookahead islands).

Entry: ``GQLParser().parse(text) -> StatusOr[SequentialSentences]``
(reference GQLParser.h).
"""
from __future__ import annotations

from typing import List, Optional

from ...common.status import Status, StatusOr
from ...filter.expressions import (AliasPropExpr, ArithmeticExpr, DestPropExpr,
                                   EdgeDstIdExpr, EdgeRankExpr, EdgeSrcIdExpr,
                                   EdgeTypeExpr, ExprError, Expression,
                                   FunctionCallExpr, InputPropExpr,
                                   LogicalExpr, PrimaryExpr, RelationalExpr,
                                   SourcePropExpr, TypeCastingExpr, UnaryExpr,
                                   VariablePropExpr)
from . import ast
from .lexer import LexError, Token, tokenize

_PSEUDO_PROPS = {"_dst", "_src", "_rank", "_type"}


class ParseError(Exception):
    pass


class _Parser:
    def __init__(self, tokens: List[Token], text: str):
        self.toks = tokens
        self.text = text
        self.i = 0

    # ---- token helpers ----------------------------------------------
    def peek(self, off: int = 0) -> Token:
        j = min(self.i + off, len(self.toks) - 1)
        return self.toks[j]

    def next(self) -> Token:
        t = self.toks[self.i]
        if t.type != "EOF":
            self.i += 1
        return t

    def _lexeme_at(self, j: int) -> str:
        """Raw source slice of token j (through the next token's
        start) — for the rare spot where a token's VALUE loses
        information the grammar needs (FLOAT "2." in a hop range)."""
        end = self.toks[j + 1].pos if j + 1 < len(self.toks) \
            else len(self.text)
        return self.text[self.toks[j].pos:end].strip()

    def at_kw(self, *kws: str) -> bool:
        t = self.peek()
        return t.type == "KW" and t.value in kws

    def at_sym(self, *syms: str) -> bool:
        t = self.peek()
        return t.type == "SYM" and t.value in syms

    def accept_kw(self, *kws: str) -> Optional[str]:
        if self.at_kw(*kws):
            return self.next().value
        return None

    def accept_sym(self, *syms: str) -> Optional[str]:
        if self.at_sym(*syms):
            return self.next().value
        return None

    def expect_kw(self, *kws: str) -> str:
        v = self.accept_kw(*kws)
        if v is None:
            self.fail(f"expected {'/'.join(k.upper() for k in kws)}")
        return v

    def expect_sym(self, sym: str) -> str:
        v = self.accept_sym(sym)
        if v is None:
            self.fail(f"expected {sym!r}")
        return v

    # keywords usable as names — the reference's unreserved_keyword set
    # (parser.yy:211-227: space/hosts/spaces/user/users/password/role/
    # roles/god/admin/guest) plus our own contextual extras
    UNRESERVED = frozenset({
        "space", "hosts", "spaces", "user", "users", "password", "role",
        "roles", "god", "admin", "guest", "balance", "data", "leader",
        "graph", "meta",
        "storage", "path", "all", "in", "out", "both", "step", "of",
        # the live-query-plane words stay usable as names — only the
        # SHOW target / statement-head positions consume them as KWs
        "queries", "timeline", "kill", "query",
    })

    def expect_id(self, what: str = "identifier") -> str:
        t = self.peek()
        if t.type == "ID":
            self.next()
            return t.value
        if t.type == "KW" and t.value in self.UNRESERVED:
            self.next()
            return t.value
        self.fail(f"expected {what}")

    def fail(self, msg: str):
        t = self.peek()
        near = self.text[max(0, t.pos - 12):t.pos + 12].replace("\n", " ")
        raise ParseError(f"syntax error near `{near.strip()}': {msg}")

    # ---- entry ------------------------------------------------------
    def parse_sentences(self) -> ast.SequentialSentences:
        out = ast.SequentialSentences()
        # optional leading PROFILE/EXPLAIN prefix applies to the whole
        # statement list (PROFILE only makes sense at position 0: the
        # trace covers the full engine pass).  The two words are NOT
        # lexer keywords (that reserved them out of expression position
        # — `ORDER BY profile` must keep parsing); they lex as plain
        # IDs and are special-cased here only as the very first token,
        # where no valid statement can start with a bare identifier.
        # Any following token starts the wrapped statement — keywords,
        # `$var =` assignments, `(` groups; a lone `PROFILE` falls
        # through to the normal error path.
        t = self.peek()
        if t.type == "ID" and isinstance(t.value, str) \
                and t.value.lower() in ("profile", "explain") \
                and self.peek(1).type != "EOF":
            self.next()
            if t.value.lower() == "profile":
                out.profile = True
                # optional FORMAT=trace suffix: the response carries
                # the flight-recorder Chrome-trace export instead of
                # the raw span tree.  FORMAT lexes as a plain ID (not
                # a keyword — same stance as PROFILE itself), so it is
                # special-cased only here, right after the prefix.
                f = self.peek()
                if f.type == "ID" and isinstance(f.value, str) \
                        and f.value.lower() == "format":
                    self.next()
                    self.expect_sym("=")
                    v = self.next()
                    if not (v.type == "ID" and isinstance(v.value, str)
                            and v.value.lower() in ("trace", "tree")):
                        self.fail("PROFILE FORMAT must be trace or tree")
                    if v.value.lower() == "trace":
                        out.profile_format = "trace"
            else:
                out.explain = True
        # optional TIMEOUT <ms> prefix (after PROFILE/EXPLAIN when both
        # are present): per-statement deadline override.  Like
        # PROFILE/EXPLAIN, `timeout` is NOT a lexer keyword — it lexes
        # as a plain ID and is special-cased only here, where no valid
        # statement can start with a bare identifier, so expressions
        # naming a `timeout` property keep parsing.
        t = self.peek()
        if t.type == "ID" and isinstance(t.value, str) \
                and t.value.lower() == "timeout" \
                and self.peek(1).type == "INT":
            self.next()
            ms = self.next().value
            if ms <= 0:
                raise ParseError("TIMEOUT must be a positive "
                                 "millisecond count")
            out.timeout_ms = int(ms)
        while True:
            while self.accept_sym(";"):
                pass
            if self.peek().type == "EOF":
                break
            out.sentences.append(self.parse_sentence())
            if self.peek().type != "EOF":
                self.expect_sym(";") if self.at_sym(";") else (
                    None if self.peek().type == "EOF" else self.fail(
                        "expected ; between statements"))
        if not out.sentences:
            raise ParseError("statement is empty")
        return out

    def parse_sentence(self) -> ast.Sentence:
        """assignment | piped/set chain."""
        t = self.peek()
        if t.type == "REF" and t.value not in ("$-", "$^", "$$") and \
                self.peek(1).type == "SYM" and self.peek(1).value == "=":
            var = self.next().value[1:]
            self.expect_sym("=")
            rhs = self.parse_combined()
            return ast.AssignmentSentence(var=var, sentence=rhs)
        return self.parse_combined()

    def parse_combined(self) -> ast.Sentence:
        """traverse (PIPE traverse | SET-op traverse)*  — left assoc."""
        left = self.parse_basic()
        while True:
            if self.accept_sym("|"):
                right = self.parse_basic()
                left = ast.PipedSentence(left=left, right=right)
            elif self.at_kw("union", "intersect", "minus"):
                op = self.next().value
                distinct = True
                if op == "union" and self.accept_kw("all"):
                    distinct = False
                right = self.parse_basic()
                left = ast.SetSentence(op=ast.SetOpKind(op), distinct=distinct,
                                       left=left, right=right)
            else:
                return left

    # ---- statement dispatch -----------------------------------------
    def parse_basic(self) -> ast.Sentence:
        if self.accept_sym("("):
            inner = self.parse_combined()
            self.expect_sym(")")
            return inner
        t = self.peek()
        if t.type != "KW":
            self.fail("expected a statement keyword")
        kw = t.value
        handler = {
            "go": self.p_go, "match": self.p_match, "find": self.p_find,
            "fetch": self.p_fetch, "yield": self.p_yield_sentence,
            "order": self.p_order_by, "limit": self.p_limit,
            "group": self.p_group_by,
            "use": self.p_use, "show": self.p_show,
            "create": self.p_create, "drop": self.p_drop,
            "alter": self.p_alter, "describe": self.p_describe,
            "desc": self.p_describe, "insert": self.p_insert,
            "update": self.p_update, "upsert": self.p_update,
            "delete": self.p_delete, "add": self.p_add_hosts,
            "remove": self.p_remove_hosts, "get": self.p_get_config,
            "balance": self.p_balance, "change": self.p_change_password,
            "grant": self.p_grant, "revoke": self.p_revoke,
            "download": self.p_download, "ingest": self.p_ingest,
            "kill": self.p_kill,
        }.get(kw)
        if handler is None:
            self.fail(f"unexpected keyword {kw.upper()}")
        return handler()

    # ---- traverse statements ----------------------------------------
    def p_go(self) -> ast.GoSentence:
        self.expect_kw("go")
        s = ast.GoSentence()
        if self.peek().type == "INT":
            n = self.next().value
            self.expect_kw("steps", "step")
            s.step = ast.StepClause(steps=n)
        elif self.accept_kw("upto"):
            n = self.next().value if self.peek().type == "INT" else self.fail(
                "expected step count")
            self.expect_kw("steps", "step")
            s.step = ast.StepClause(steps=n, upto=True)
        s.from_ = self.p_from_clause()
        if self.at_kw("over"):
            s.over = self.p_over_clause()
        if self.at_kw("where"):
            s.where = ast.WhereClause(filter=self.p_where())
        if self.at_kw("yield"):
            s.yield_ = self.p_yield_clause()
        return s

    def p_from_clause(self) -> ast.FromClause:
        self.expect_kw("from")
        return self.p_vid_list_or_ref()

    def p_vid_list_or_ref(self) -> ast.FromClause:
        fc = ast.FromClause()
        t = self.peek()
        if t.type == "REF":
            fc.ref = self.p_ref_expr()
        else:
            fc.vids = [self.p_expression()]
            while self.accept_sym(","):
                fc.vids.append(self.p_expression())
        return fc

    def p_over_clause(self) -> ast.OverClause:
        self.expect_kw("over")
        oc = ast.OverClause()
        if self.accept_sym("*"):
            oc.is_all = True
        else:
            while True:
                name = self.expect_id("edge name")
                alias = None
                if self.accept_kw("as"):
                    alias = self.expect_id("alias")
                oc.edges.append(ast.OverEdge(edge=name, alias=alias))
                if not self.accept_sym(","):
                    break
        if self.accept_kw("reversely"):
            oc.reversely = True
            if self.at_kw("bidirect"):
                self.fail("REVERSELY and BIDIRECT exclude each other")
        elif self.accept_kw("bidirect"):
            oc.bidirect = True
            if self.at_kw("reversely"):
                self.fail("REVERSELY and BIDIRECT exclude each other")
        return oc

    def p_where(self) -> Expression:
        self.expect_kw("where")
        return self.p_expression()

    def p_yield_clause(self) -> ast.YieldClause:
        self.expect_kw("yield")
        yc = ast.YieldClause()
        if self.accept_kw("distinct"):
            yc.distinct = True
        while True:
            expr = self.p_expression()
            alias = None
            if self.accept_kw("as"):
                alias = self.expect_id("column alias")
            yc.columns.append(ast.YieldColumn(expr=expr, alias=alias))
            if not self.accept_sym(","):
                break
        return yc

    def p_yield_sentence(self) -> ast.YieldSentence:
        yc = self.p_yield_clause()
        s = ast.YieldSentence(yield_=yc)
        if self.at_kw("where"):
            s.where = ast.WhereClause(filter=self.p_where())
        return s

    def p_order_by(self) -> ast.OrderBySentence:
        self.expect_kw("order")
        self.expect_kw("by")
        s = ast.OrderBySentence()
        while True:
            expr = self.p_expression()
            asc = True
            if self.accept_kw("desc"):
                asc = False
            elif self.accept_kw("asc"):
                asc = True
            s.factors.append(ast.OrderFactor(expr=expr, ascending=asc))
            if not self.accept_sym(","):
                break
        return s

    def p_limit(self) -> ast.LimitSentence:
        self.expect_kw("limit")
        first = self.next()
        if first.type != "INT":
            self.fail("expected integer")
        if self.accept_sym(","):
            second = self.next()
            if second.type != "INT":
                self.fail("expected integer")
            return ast.LimitSentence(offset=first.value, count=second.value)
        if self.accept_kw("offset"):
            off = self.next()
            if off.type != "INT":
                self.fail("expected integer")
            return ast.LimitSentence(offset=off.value, count=first.value)
        return ast.LimitSentence(offset=0, count=first.value)

    def p_group_by(self) -> ast.GroupBySentence:
        self.expect_kw("group")
        self.expect_kw("by")
        s = ast.GroupBySentence()
        while True:
            expr = self.p_expression()
            s.group_cols.append(ast.YieldColumn(expr=expr))
            if not self.accept_sym(","):
                break
        if self.at_kw("yield"):
            s.yield_ = self.p_yield_clause()
        return s

    def p_match(self) -> ast.MatchSentence:
        start = self.peek().pos
        self.expect_kw("match")
        save = self.i
        try:
            s = self._p_match_basic()
            s.raw = self.text[start:self.peek().pos]
            return s
        except ParseError:
            self.i = save     # not the basic pattern: raw fallback
        depth = 0
        while not (self.peek().type == "EOF" or
                   (depth == 0 and self.at_sym(";", "|"))):
            if self.at_sym("("):
                depth += 1
            elif self.at_sym(")"):
                depth -= 1
            self.next()
        return ast.MatchSentence(raw=self.text[start:self.peek().pos])

    def _at_return(self) -> bool:
        t = self.peek()
        return t.type == "ID" and t.value.lower() == "return"

    def _p_match_basic(self) -> ast.MatchSentence:
        """(a[:label])-[e:etype]->(b[:label]) [WHERE ...] RETURN cols —
        or the reverse form (a)<-[e:etype]-(b) — the MATCH shapes the
        GO planner serves (executors/traverse.MatchExecutor lowers
        them)."""
        s = ast.MatchSentence()
        self.expect_sym("(")
        s.a_var = self.expect_id("pattern variable")
        if self.accept_sym(":"):
            s.a_label = self.expect_id("tag label")
        self.expect_sym(")")
        # "<-" lexes as two symbols; a leading "<" marks the reverse
        # pattern (the edge runs b -> a) closed by "-" instead of "->"
        if self.accept_sym("<"):
            s.reverse = True
        self.expect_sym("-")
        self.expect_sym("[")
        s.e_var = self.expect_id("edge variable")
        if self.accept_sym(":"):
            s.e_label = self.expect_id("edge type")
        if self.accept_sym("*"):
            # variable length: *N (exact) or *m..N.  The lexer reads
            # an unspaced "m..N" as two FLOATs ("m." and ".N"), so the
            # bounds are reconstructed from the raw lexemes; a spaced
            # "m .. N" arrives as INT SYM(.) SYM(.) INT.  Bounds are
            # validated by the executor.
            t = self.peek()
            if t.type == "INT":
                s.hop_min = s.hop_max = self.next().value
                if self.accept_sym("."):
                    self.expect_sym(".")
                    if self.peek().type != "INT":
                        self.fail("expected upper hop bound after ..")
                    s.hop_max = self.next().value
            elif t.type == "FLOAT":
                lo_lex = self._lexeme_at(self.i)
                self.next()
                hi = self.peek()
                hi_lex = self._lexeme_at(self.i)
                if not (lo_lex.endswith(".") and hi.type == "FLOAT"
                        and hi_lex.startswith(".")
                        and lo_lex[:-1].isdigit()
                        and hi_lex[1:].isdigit()):
                    self.fail("expected hop range *m..N")
                self.next()
                s.hop_min = int(lo_lex[:-1])
                s.hop_max = int(hi_lex[1:])
            else:
                self.fail("expected hop count after *")
        self.expect_sym("]")
        if s.reverse:
            self.expect_sym("-")
        else:
            self.expect_sym("->")
        self.expect_sym("(")
        s.b_var = self.expect_id("pattern variable")
        if self.accept_sym(":"):
            s.b_label = self.expect_id("tag label")
        self.expect_sym(")")
        if self.accept_kw("where"):
            w0 = self.peek().pos
            depth = 0
            while not (self.peek().type == "EOF"
                       or (depth == 0 and (self._at_return()
                                           or self.at_sym(";", "|")))):
                if self.at_sym("(", "["):
                    depth += 1
                elif self.at_sym(")", "]"):
                    depth -= 1
                self.next()
            s.where_text = self.text[w0:self.peek().pos].strip()
            if not s.where_text:
                self.fail("empty WHERE in MATCH")
        if not self._at_return():
            self.fail("expected RETURN")
        self.next()
        r0 = self.peek().pos
        depth = 0
        while not (self.peek().type == "EOF"
                   or (depth == 0 and self.at_sym(";", "|"))):
            if self.at_sym("(", "["):
                depth += 1
            elif self.at_sym(")", "]"):
                depth -= 1
            self.next()
        s.return_text = self.text[r0:self.peek().pos].strip()
        if not s.return_text:
            self.fail("empty RETURN in MATCH")
        return s

    def p_find(self) -> ast.Sentence:
        self.expect_kw("find")
        if self.at_kw("shortest", "all"):
            shortest = self.next().value == "shortest"
            self.expect_kw("path")
            s = ast.FindPathSentence(shortest=shortest)
            s.from_ = self.p_from_clause()
            self.expect_kw("to")
            s.to = self.p_vid_list_or_ref()
            if self.at_kw("over"):
                s.over = self.p_over_clause()
            if self.accept_kw("upto"):
                n = self.next()
                if n.type != "INT":
                    self.fail("expected step count")
                self.expect_kw("steps", "step")
                s.upto = ast.StepClause(steps=n.value, upto=True)
            return s
        # legacy FIND <props> FROM ... (reference stub FindSentence)
        s2 = ast.FindSentence()
        s2.props.append(self.expect_id("property"))
        while self.accept_sym(","):
            s2.props.append(self.expect_id("property"))
        s2.from_ = self.p_from_clause()
        if self.at_kw("where"):
            s2.where = ast.WhereClause(filter=self.p_where())
        return s2

    def p_fetch(self) -> ast.Sentence:
        self.expect_kw("fetch")
        self.expect_kw("prop")
        self.expect_kw("on")
        if self.accept_kw("edge"):
            return self._fetch_edges(self.expect_id("edge name"))
        # FETCH PROP ON <tag|*> vids | ON <edge> key->key
        if self.accept_sym("*"):
            name = "*"
        else:
            name = self.expect_id("tag or edge name")
        # edge fetch if next tokens look like src->dst
        save = self.i
        if self.peek().type in ("INT", "REF", "ID", "STRING") :
            # lookahead for `->` to distinguish edge fetch
            j = self.i
            depth = 0
            is_edge = False
            while j < len(self.toks):
                tt = self.toks[j]
                if tt.type == "SYM" and tt.value == "->" and depth == 0:
                    is_edge = True
                    break
                if tt.type == "SYM" and tt.value == "(":
                    depth += 1
                elif tt.type == "SYM" and tt.value == ")":
                    depth -= 1
                elif tt.type in ("KW", "EOF") or (tt.type == "SYM" and
                                                  tt.value in (";", "|")):
                    break
                j += 1
            if is_edge:
                return self._fetch_edges(name)
        self.i = save
        s = ast.FetchVerticesSentence(tag=name)
        s.from_ = self.p_vid_list_or_ref()
        if self.at_kw("yield"):
            s.yield_ = self.p_yield_clause()
        return s

    def _fetch_edges(self, name: str) -> ast.FetchEdgesSentence:
        s = ast.FetchEdgesSentence(edge=name)
        if self.peek().type == "REF":
            src = self.p_ref_expr()
            self.expect_sym("->")
            dst = self.p_ref_expr()
            s.ref = (src, dst)
        else:
            while True:
                src = self.p_expression()
                self.expect_sym("->")
                dst = self.p_expression()
                rank = 0
                if self.accept_sym("@"):
                    rt = self.next()
                    if rt.type != "INT":
                        self.fail("expected rank")
                    rank = rt.value
                s.keys.append(ast.EdgeKeyRef(src=src, dst=dst, rank=rank))
                if not self.accept_sym(","):
                    break
        if self.at_kw("yield"):
            s.yield_ = self.p_yield_clause()
        return s

    # ---- mutate -----------------------------------------------------
    def p_insert(self) -> ast.Sentence:
        self.expect_kw("insert")
        if self.accept_kw("vertex"):
            return self._insert_vertex()
        self.expect_kw("edge")
        return self._insert_edge()

    def _insert_vertex(self) -> ast.InsertVertexSentence:
        s = ast.InsertVertexSentence()
        if self.accept_kw("no"):
            self.expect_kw("overwrite")
            s.overwritable = False
        while True:
            tag = self.expect_id("tag name")
            props: List[str] = []
            self.expect_sym("(")
            if not self.at_sym(")"):
                while True:
                    props.append(self.expect_id("property"))
                    if not self.accept_sym(","):
                        break
            self.expect_sym(")")
            s.tags.append(ast.TagItem(name=tag, props=props))
            if not self.accept_sym(","):
                break
        self.expect_kw("values")
        while True:
            vid = self.p_expression()
            self.expect_sym(":")
            self.expect_sym("(")
            values: List[Expression] = []
            if not self.at_sym(")"):
                while True:
                    values.append(self.p_expression())
                    if not self.accept_sym(","):
                        break
            self.expect_sym(")")
            s.rows.append(ast.VertexRowItem(vid=vid, values=values))
            if not self.accept_sym(","):
                break
        return s

    def _insert_edge(self) -> ast.InsertEdgeSentence:
        s = ast.InsertEdgeSentence()
        if self.accept_kw("no"):
            self.expect_kw("overwrite")
            s.overwritable = False
        s.edge = self.expect_id("edge name")
        self.expect_sym("(")
        if not self.at_sym(")"):
            while True:
                s.props.append(self.expect_id("property"))
                if not self.accept_sym(","):
                    break
        self.expect_sym(")")
        self.expect_kw("values")
        while True:
            src = self.p_expression()
            self.expect_sym("->")
            dst = self.p_expression()
            rank = 0
            if self.accept_sym("@"):
                rt = self.next()
                if rt.type != "INT":
                    self.fail("expected rank")
                rank = rt.value
            self.expect_sym(":")
            self.expect_sym("(")
            values: List[Expression] = []
            if not self.at_sym(")"):
                while True:
                    values.append(self.p_expression())
                    if not self.accept_sym(","):
                        break
            self.expect_sym(")")
            s.rows.append(ast.EdgeRowItem(src=src, dst=dst, rank=rank,
                                          values=values))
            if not self.accept_sym(","):
                break
        return s

    def p_update(self) -> ast.Sentence:
        insertable = self.next().value == "upsert"
        if self.accept_kw("or"):              # UPDATE OR INSERT (parser.yy
            self.expect_kw("insert")          # update_*_sentence variants)
            insertable = True
        if self.accept_kw("configs", "variables"):
            # UPDATE CONFIGS|VARIABLES [module:]name = value
            module, name = self._config_item()
            self.expect_sym("=")
            return ast.ConfigSentence(action="update", module=module,
                                      name=name, value=self._prop_value())
        if self.accept_kw("vertex"):
            s = ast.UpdateVertexSentence(insertable=insertable)
            s.vid = self.p_expression()
            self.expect_kw("set")
            s.items = self._update_items()
            if self.at_kw("when", "where"):
                self.next()
                s.where = ast.WhereClause(filter=self.p_expression())
            if self.at_kw("yield"):
                s.yield_ = self.p_yield_clause()
            return s
        self.expect_kw("edge")
        s2 = ast.UpdateEdgeSentence(insertable=insertable)
        s2.src = self.p_expression()
        self.expect_sym("->")
        s2.dst = self.p_expression()
        if self.accept_sym("@"):
            rt = self.next()
            if rt.type != "INT":
                self.fail("expected rank")
            s2.rank = rt.value
        # the reference addresses the edge purely by key (update_edge
        # parser.yy:1108: no edge name); our extended form allows
        # `OF <edge>` to disambiguate explicitly
        if self.accept_kw("of"):
            s2.edge = self.expect_id("edge name")
        self.expect_kw("set")
        s2.items = self._update_items()
        if self.at_kw("when", "where"):
            self.next()
            s2.where = ast.WhereClause(filter=self.p_expression())
        if self.at_kw("yield"):
            s2.yield_ = self.p_yield_clause()
        return s2

    def _update_items(self) -> List[ast.UpdateItem]:
        items = []
        while True:
            prop = self.expect_id("property")
            if self.accept_sym("."):  # tag.prop form
                prop = self.expect_id("property")
            self.expect_sym("=")
            items.append(ast.UpdateItem(prop=prop, value=self.p_expression()))
            if not self.accept_sym(","):
                break
        return items

    def p_delete(self) -> ast.Sentence:
        self.expect_kw("delete")
        if self.accept_kw("vertex"):
            s = ast.DeleteVertexSentence()
            s.vids = [self.p_expression()]
            while self.accept_sym(","):
                s.vids.append(self.p_expression())
            if self.at_kw("where"):
                s.where = ast.WhereClause(filter=self.p_where())
            return s
        self.expect_kw("edge")
        s2 = ast.DeleteEdgeSentence()
        # the reference's form carries no edge name (delete_edge_sentence
        # parser.yy:1182-1188: DELETE EDGE <src> -> <dst>, ...); our
        # extended form names the edge type first
        t = self.peek()
        if (t.type == "ID" or (t.type == "KW" and t.value in self.UNRESERVED)) \
                and not (self.peek(1).type == "SYM"
                         and self.peek(1).value == "("):
            s2.edge = self.expect_id("edge name")
        while True:
            src = self.p_expression()
            self.expect_sym("->")
            dst = self.p_expression()
            rank = 0
            if self.accept_sym("@"):
                rt = self.next()
                if rt.type != "INT":
                    self.fail("expected rank")
                rank = rt.value
            s2.keys.append(ast.EdgeKeyRef(src=src, dst=dst, rank=rank))
            if not self.accept_sym(","):
                break
        if self.at_kw("where"):
            s2.where = ast.WhereClause(filter=self.p_where())
        return s2

    # ---- maintain ---------------------------------------------------
    def _if_not_exists(self) -> bool:
        if self.accept_kw("if"):
            self.expect_kw("not")
            self.expect_kw("exists")
            return True
        return False

    def _if_exists(self) -> bool:
        if self.accept_kw("if"):
            self.expect_kw("exists")
            return True
        return False

    def p_create(self) -> ast.Sentence:
        self.expect_kw("create")
        if self.accept_kw("space"):
            ine = self._if_not_exists()
            s = ast.CreateSpaceSentence(if_not_exists=ine)
            s.name = self.expect_id("space name")
            if self.accept_sym("("):
                while not self.at_sym(")"):
                    pname = self.expect_id("space option")
                    self.expect_sym("=")
                    s.props.append(ast.SchemaPropItem(
                        name=pname, value=self._prop_value()))
                    if not self.accept_sym(","):
                        break
                self.expect_sym(")")
            return s
        if self.accept_kw("user"):
            ine = self._if_not_exists()
            account = self.expect_id("account")
            self.expect_kw("with")
            self.expect_kw("password")
            pw = self.next()
            if pw.type != "STRING":
                self.fail("expected password string")
            return ast.CreateUserSentence(account=account, password=pw.value,
                                          if_not_exists=ine)
        is_tag = self.accept_kw("tag") is not None
        if not is_tag:
            self.expect_kw("edge")
        ine = self._if_not_exists()
        cls = ast.CreateTagSentence if is_tag else ast.CreateEdgeSentence
        s = cls(name=self.expect_id("schema name"))
        s.if_not_exists = ine
        self.expect_sym("(")
        # empty column lists and trailing commas are legal
        # (create_tag_sentence parser.yy:713-732)
        while not self.at_sym(")"):
            s.columns.append(self._column_spec())
            if not self.accept_sym(","):
                break
        self.expect_sym(")")
        # schema props: ttl_duration = n, ttl_col = name
        while self.peek().type == "ID" or self.at_sym(","):
            if self.accept_sym(","):
                continue
            pname = self.expect_id("schema property")
            self.expect_sym("=")
            s.props.append(ast.SchemaPropItem(name=pname,
                                              value=self._prop_value()))
        return s

    def _column_spec(self) -> ast.ColumnSpec:
        name = self.expect_id("column name")
        t = self.peek()
        if t.type == "KW" and t.value in ("int", "double", "string", "bool",
                                          "timestamp"):
            self.next()
            default = None
            if self.peek().type == "ID" and \
                    self.peek().value.lower() == "default":
                self.next()
                default = self._prop_value()
            return ast.ColumnSpec(name=name, type_name=t.value, default=default)
        self.fail("expected column type")

    def _prop_value(self):
        t = self.next()
        if t.type in ("INT", "FLOAT", "STRING"):
            return t.value
        if t.type == "KW" and t.value in ("true", "false"):
            return t.value == "true"
        if t.type == "ID":
            return t.value
        self.fail("expected literal value")

    def p_drop(self) -> ast.Sentence:
        self.expect_kw("drop")
        if self.accept_kw("space"):
            ife = self._if_exists()
            return ast.DropSpaceSentence(name=self.expect_id("space"),
                                         if_exists=ife)
        if self.accept_kw("user"):
            ife = self._if_exists()
            return ast.DropUserSentence(account=self.expect_id("account"),
                                        if_exists=ife)
        if self.accept_kw("tag"):
            ife = self._if_exists()
            return ast.DropTagSentence(name=self.expect_id("tag"),
                                       if_exists=ife)
        self.expect_kw("edge")
        ife = self._if_exists()
        return ast.DropEdgeSentence(name=self.expect_id("edge"), if_exists=ife)

    def p_alter(self) -> ast.Sentence:
        self.expect_kw("alter")
        if self.accept_kw("user"):
            account = self.expect_id("account")
            self.expect_kw("with")
            self.expect_kw("password")
            pw = self.next()
            if pw.type != "STRING":
                self.fail("expected password string")
            return ast.AlterUserSentence(account=account, password=pw.value)
        is_tag = self.accept_kw("tag") is not None
        if not is_tag:
            self.expect_kw("edge")
        cls = ast.AlterTagSentence if is_tag else ast.AlterEdgeSentence
        s = cls(name=self.expect_id("schema name"))
        while True:
            if self.accept_kw("add"):
                op = "ADD"
            elif self.accept_kw("change"):
                op = "CHANGE"
            elif self.accept_kw("drop"):
                op = "DROP"
            else:
                break
            cols: List[ast.ColumnSpec] = []
            self.expect_sym("(")
            while not self.at_sym(")"):
                if op == "DROP":
                    cols.append(ast.ColumnSpec(
                        name=self.expect_id("column"), type_name="int"))
                else:
                    cols.append(self._column_spec())
                if not self.accept_sym(","):
                    break
            self.expect_sym(")")
            s.items.append(ast.AlterSchemaOptItem(op=op, columns=cols))
            if not self.accept_sym(","):
                break
        while self.peek().type == "ID":  # ttl props
            pname = self.expect_id("schema property")
            self.expect_sym("=")
            s.props.append(ast.SchemaPropItem(name=pname,
                                              value=self._prop_value()))
            if not self.accept_sym(","):
                break
        return s

    def p_describe(self) -> ast.Sentence:
        self.next()  # describe / desc
        if self.accept_kw("space"):
            return ast.DescribeSpaceSentence(name=self.expect_id("space"))
        if self.accept_kw("tag"):
            return ast.DescribeTagSentence(name=self.expect_id("tag"))
        self.expect_kw("edge")
        return ast.DescribeEdgeSentence(name=self.expect_id("edge"))

    # ---- admin ------------------------------------------------------
    def p_use(self) -> ast.UseSentence:
        self.expect_kw("use")
        return ast.UseSentence(space=self.expect_id("space name"))

    def p_show(self) -> ast.Sentence:
        self.expect_kw("show")
        # SHOW VARIABLES is the reference's alias for SHOW CONFIGS
        # (parser.yy:1219-1221)
        if self.accept_kw("configs", "variables"):
            module = None
            if self.at_kw("graph", "meta", "storage"):
                module = self.next().value
            return ast.ConfigSentence(action="show", module=module)
        if self.accept_kw("create"):          # parser.yy:1222-1230
            if self.accept_kw("space"):
                target = ast.ShowTarget.CREATE_SPACE
            elif self.accept_kw("tag"):
                target = ast.ShowTarget.CREATE_TAG
            else:
                self.expect_kw("edge")
                target = ast.ShowTarget.CREATE_EDGE
            return ast.ShowSentence(target=target,
                                    name=self.expect_id("name"))
        if self.accept_kw("user"):
            return ast.ShowSentence(target=ast.ShowTarget.USER,
                                    name=self.expect_id("account"))
        if self.accept_kw("roles"):
            self.expect_kw("in")
            return ast.ShowSentence(target=ast.ShowTarget.ROLES,
                                    name=self.expect_id("space name"))
        mapping = {"spaces": ast.ShowTarget.SPACES, "tags": ast.ShowTarget.TAGS,
                   "edges": ast.ShowTarget.EDGES, "hosts": ast.ShowTarget.HOSTS,
                   "parts": ast.ShowTarget.PARTS, "users": ast.ShowTarget.USERS,
                   "stats": ast.ShowTarget.STATS,
                   "events": ast.ShowTarget.EVENTS,
                   "queries": ast.ShowTarget.QUERIES,
                   "timeline": ast.ShowTarget.TIMELINE}
        kw = self.next()
        if kw.type != "KW" or kw.value not in mapping:
            self.fail("expected SHOW target")
        count = None
        if kw.value == "timeline" and self.peek().type == "INT":
            # SHOW TIMELINE <n>: cap the per-replica record fan-out
            count = int(self.next().value)
            if count <= 0:
                self.fail("SHOW TIMELINE count must be positive")
        return ast.ShowSentence(target=mapping[kw.value], count=count)

    def p_kill(self) -> ast.KillQuerySentence:
        self.expect_kw("kill")
        self.expect_kw("query")
        t = self.peek()
        if t.type != "INT":
            self.fail("expected query id after KILL QUERY")
        self.next()
        return ast.KillQuerySentence(qid=t.value)

    def _host_list(self) -> List[str]:
        """Quoted "ip:port" strings or bare 127.0.0.1:port literals
        (host_item parser.yy; trailing commas tolerated like host_list)."""
        hosts = []
        while True:
            t = self.peek()
            if t.type == "STRING":
                self.next()
                hosts.append(t.value)
            elif t.type == "IPV4":
                self.next()
                self.expect_sym(":")
                pt = self.next()
                if pt.type != "INT":
                    self.fail("expected port")
                hosts.append(f"{t.value}:{pt.value}")
            elif hosts:                        # trailing comma case
                break
            else:
                self.fail('expected "ip:port"')
            if not self.accept_sym(","):
                break
        return hosts

    def p_add_hosts(self) -> ast.AddHostsSentence:
        self.expect_kw("add")
        self.expect_kw("hosts")
        return ast.AddHostsSentence(hosts=self._host_list())

    def p_remove_hosts(self) -> ast.RemoveHostsSentence:
        self.expect_kw("remove")
        self.expect_kw("hosts")
        return ast.RemoveHostsSentence(hosts=self._host_list())

    def p_get_config(self) -> ast.ConfigSentence:
        self.expect_kw("get")
        self.expect_kw("configs", "variables")   # VARIABLES = alias
        module, name = self._config_item()
        return ast.ConfigSentence(action="get", module=module, name=name)

    def _config_item(self):
        module = None
        if self.at_kw("graph", "meta", "storage"):
            module = self.next().value
            self.expect_sym(":")
        name = self.expect_id("config name")
        return module, name

    def p_balance(self) -> ast.BalanceSentence:
        self.expect_kw("balance")
        if self.accept_kw("leader"):
            return ast.BalanceSentence(target="leader")
        self.expect_kw("data")
        if self.accept_kw("stop"):
            return ast.BalanceSentence(target="data", stop=True)
        if self.peek().type == "INT":
            return ast.BalanceSentence(target="data",
                                       plan_id=self.next().value)
        return ast.BalanceSentence(target="data")

    def p_change_password(self) -> ast.ChangePasswordSentence:
        self.expect_kw("change")
        self.expect_kw("password")
        account = self.expect_id("account")
        old = None
        if self.accept_kw("from"):
            t = self.next()
            if t.type != "STRING":
                self.fail("expected old password")
            old = t.value
        self.expect_kw("to")
        t = self.next()
        if t.type != "STRING":
            self.fail("expected new password")
        return ast.ChangePasswordSentence(account=account, old_password=old,
                                          new_password=t.value)

    def _role(self) -> str:
        t = self.next()
        if t.type == "KW" and t.value in ("god", "admin", "user", "guest"):
            return t.value.upper()
        self.fail("expected role GOD/ADMIN/USER/GUEST")

    def p_grant(self) -> ast.GrantSentence:
        self.expect_kw("grant")
        self.accept_kw("role")
        role = self._role()
        self.expect_kw("on")
        space = self.expect_id("space")
        self.expect_kw("to")
        return ast.GrantSentence(role=role, space=space,
                                 account=self.expect_id("account"))

    def p_revoke(self) -> ast.RevokeSentence:
        self.expect_kw("revoke")
        self.accept_kw("role")
        role = self._role()
        self.expect_kw("on")
        space = self.expect_id("space")
        self.expect_kw("from")
        return ast.RevokeSentence(role=role, space=space,
                                  account=self.expect_id("account"))

    def p_download(self) -> ast.DownloadSentence:
        self.expect_kw("download")
        self.expect_kw("hdfs")
        t = self.next()
        if t.type != "STRING":
            self.fail("expected hdfs url string")
        return ast.DownloadSentence(url=t.value)

    def p_ingest(self) -> ast.IngestSentence:
        self.expect_kw("ingest")
        return ast.IngestSentence()

    # ================= expressions =================
    def p_expression(self) -> Expression:
        return self.p_logical_or()

    def p_logical_or(self) -> Expression:
        left = self.p_logical_and()
        while self.accept_sym("||") or self.accept_kw("or"):
            left = LogicalExpr("||", left, self.p_logical_and())
        return left

    def p_logical_and(self) -> Expression:
        left = self.p_relational()
        while self.accept_sym("&&") or self.accept_kw("and"):
            left = LogicalExpr("&&", left, self.p_relational())
        return left

    def p_relational(self) -> Expression:
        left = self.p_additive()
        while self.at_sym("<", "<=", ">", ">=", "==", "!="):
            op = self.next().value
            left = RelationalExpr(op, left, self.p_additive())
        return left

    def p_additive(self) -> Expression:
        left = self.p_multiplicative()
        while self.at_sym("+", "-"):
            op = self.next().value
            left = ArithmeticExpr(op, left, self.p_multiplicative())
        return left

    def p_multiplicative(self) -> Expression:
        left = self.p_xor()
        while self.at_sym("*", "/", "%"):
            op = self.next().value
            left = ArithmeticExpr(op, left, self.p_xor())
        return left

    def p_xor(self) -> Expression:
        left = self.p_unary()
        while self.accept_sym("^") or self.accept_kw("xor"):
            left = ArithmeticExpr("^", left, self.p_unary())
        return left

    def p_unary(self) -> Expression:
        if self.at_sym("-", "+", "!"):
            op = self.next().value
            return UnaryExpr(op, self.p_unary())
        if self.accept_kw("not"):
            return UnaryExpr("!", self.p_unary())
        return self.p_primary()

    def p_primary(self) -> Expression:
        t = self.peek()
        # cast: (int)expr  (double)x ...
        if t.type == "SYM" and t.value == "(" and \
                self.peek(1).type == "KW" and \
                self.peek(1).value in ("int", "double", "string", "bool") and \
                self.peek(2).type == "SYM" and self.peek(2).value == ")":
            self.next()
            type_name = self.next().value
            self.next()
            return TypeCastingExpr(type_name, self.p_unary())
        if self.accept_sym("("):
            inner = self.p_expression()
            self.expect_sym(")")
            return inner
        if t.type == "INT" or t.type == "FLOAT" or t.type == "STRING":
            self.next()
            return PrimaryExpr(t.value)
        if t.type == "KW" and t.value in ("true", "false"):
            self.next()
            return PrimaryExpr(t.value == "true")
        if t.type == "REF":
            return self.p_ref_expr()
        if t.type == "ID" or (t.type == "KW" and
                              self.peek(1).type == "SYM" and
                              self.peek(1).value in ("(", ".")):
            return self.p_name_expr()
        self.fail("expected an expression")

    def p_ref_expr(self) -> Expression:
        t = self.next()
        ref = t.value
        if ref == "$-":
            # $-.prop  or bare $- (the input id column)
            if self.accept_sym("."):
                return InputPropExpr(self.expect_id("input column"))
            return InputPropExpr("id")
        if ref == "$^":
            self.expect_sym(".")
            tag = self.expect_id("tag")
            self.expect_sym(".")
            return SourcePropExpr(tag, self.expect_id("property"))
        if ref == "$$":
            self.expect_sym(".")
            tag = self.expect_id("tag")
            self.expect_sym(".")
            return DestPropExpr(tag, self.expect_id("property"))
        var = ref[1:]
        if self.accept_sym("."):
            return VariablePropExpr(var, self.expect_id("column"))
        return VariablePropExpr(var, "id")

    def p_name_expr(self) -> Expression:
        name = self.expect_id("name")
        if self.accept_sym("("):
            # COUNT(*): the canonical aggregate spelling — equivalent
            # to the no-arg form (one tally per input row,
            # _aggregate_rows).  COUNT only: SUM(*)/AVG(*) have no
            # defined meaning and must stay parse errors
            if name.lower() == "count" and self.accept_sym("*"):
                self.expect_sym(")")
                return FunctionCallExpr(name, [])
            args: List[Expression] = []
            if not self.at_sym(")"):
                while True:
                    args.append(self.p_expression())
                    if not self.accept_sym(","):
                        break
            self.expect_sym(")")
            return FunctionCallExpr(name, args)
        if self.accept_sym("."):
            prop = self.expect_id("property")
            if prop == "_dst":
                return EdgeDstIdExpr(name)
            if prop == "_src":
                return EdgeSrcIdExpr(name)
            if prop == "_rank":
                return EdgeRankExpr(name)
            if prop == "_type":
                return EdgeTypeExpr(name)
            return AliasPropExpr(name, prop)
        # bare identifier — treat as alias-less input column (YIELD name)
        return InputPropExpr(name)


class GQLParser:
    """parse(text) -> StatusOr[SequentialSentences] (reference GQLParser.h)."""

    def parse(self, text: str) -> StatusOr[ast.SequentialSentences]:
        try:
            tokens = tokenize(text)
            p = _Parser(tokens, text)
            return StatusOr.of(p.parse_sentences())
        except (ParseError, LexError, ExprError) as e:
            return StatusOr.error(Status.SyntaxError(str(e)))
