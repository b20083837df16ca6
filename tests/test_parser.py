"""Parser tests — modeled on the reference's ParserTest.cpp/ScannerTest.cpp
(SURVEY.md §4): every sentence family parses to the right AST."""
import pytest

from nebula_tpu.graph.parser import GQLParser, ast
from nebula_tpu.filter.expressions import (AliasPropExpr, InputPropExpr,
                                           PrimaryExpr, RelationalExpr,
                                           SourcePropExpr)

P = GQLParser()


def parse1(text):
    r = P.parse(text)
    assert r.ok(), r.status
    assert len(r.value().sentences) == 1
    return r.value().sentences[0]


def parse_err(text):
    r = P.parse(text)
    assert not r.ok()
    return r.status


class TestGo:
    def test_minimal(self):
        s = parse1("GO FROM 1 OVER follow")
        assert isinstance(s, ast.GoSentence)
        assert s.step.steps == 1
        assert [e.value for e in s.from_.vids] == [1]
        assert s.over.edges[0].edge == "follow"
        assert not s.over.reversely

    def test_steps_where_yield(self):
        s = parse1('GO 3 STEPS FROM 1,2,3 OVER follow WHERE $^.player.age > 30 '
                   'YIELD follow._dst AS d, $^.player.name')
        assert s.step.steps == 3
        assert len(s.from_.vids) == 3
        assert isinstance(s.where.filter, RelationalExpr)
        assert len(s.yield_.columns) == 2
        assert s.yield_.columns[0].alias == "d"

    def test_over_multi_and_all(self):
        s = parse1("GO FROM 1 OVER follow, serve REVERSELY")
        assert [e.edge for e in s.over.edges] == ["follow", "serve"]
        assert s.over.reversely
        s2 = parse1("GO FROM 1 OVER *")
        assert s2.over.is_all

    @pytest.mark.parametrize("over, edges, is_all", [
        ("OVER follow BIDIRECT", [("follow", None)], False),
        ("OVER follow, serve BIDIRECT",
         [("follow", None), ("serve", None)], False),
        ("OVER * BIDIRECT", [], True),
        ("OVER follow AS f, serve AS s BIDIRECT",
         [("follow", "f"), ("serve", "s")], False),
        ("OVER follow bidirect", [("follow", None)], False),
    ])
    def test_over_bidirect(self, over, edges, is_all):
        s = parse1(f"GO 2 STEPS FROM 1 {over} WHERE 1 > 0 "
                   f"YIELD DISTINCT follow._dst")
        assert s.over.bidirect and not s.over.reversely
        assert s.over.is_all == is_all
        assert [(e.edge, e.alias) for e in s.over.edges] == edges
        assert s.over.signs() == (-1, 1)
        assert s.where is not None and s.yield_.distinct
        # the clause's own rendering parses to the same clause
        again = parse1(f"GO FROM 1 {s.over}")
        assert again.over == s.over and str(again.over) == str(s.over)
        assert str(s.over).endswith(" BIDIRECT")

    @pytest.mark.parametrize("head", [
        "GO FROM 1", "FIND SHORTEST PATH FROM 1 TO 2",
        "FIND ALL PATH FROM 1 TO 2"])
    @pytest.mark.parametrize("word, signs", [
        ("", (1,)), (" REVERSELY", (-1,)), (" BIDIRECT", (-1, 1))])
    def test_over_clause_round_trips(self, word, signs, head):
        s = parse1(f"{head} OVER follow AS f, serve{word}")
        assert str(s.over) == f"OVER follow AS f, serve{word}"
        assert s.over.signs() == signs
        assert parse1(f"{head} {s.over}").over == s.over

    @pytest.mark.parametrize("word, signs", [
        (" REVERSELY", (-1,)), (" BIDIRECT", (-1, 1))])
    def test_find_path_takes_the_word_before_upto(self, word, signs):
        s = parse1(f"FIND SHORTEST PATH FROM 1 TO 2, 3 OVER *{word} "
                   f"UPTO 4 STEPS")
        assert s.kind == ast.Kind.FIND_PATH and s.shortest
        assert s.over.is_all and s.over.signs() == signs
        assert s.upto.steps == 4

    @pytest.mark.parametrize("text, why", [
        ("GO FROM 1 OVER follow REVERSELY BIDIRECT", "exclude each other"),
        ("GO FROM 1 OVER follow BIDIRECT REVERSELY", "exclude each other"),
        ("GO FROM 1 OVER * REVERSELY BIDIRECT", "exclude each other"),
        ("GO FROM 1 OVER BIDIRECT", "edge name"),
        ("FIND SHORTEST PATH FROM 1 TO 2 OVER follow BIDIRECT REVERSELY",
         "exclude each other"),
    ])
    def test_bidirect_refused(self, text, why):
        assert why in parse_err(text).msg

    def test_bidirect_is_a_keyword_of_the_console_too(self):
        from nebula_tpu.console.repl import KEYWORDS
        from nebula_tpu.graph.parser.lexer import KEYWORDS as LEXED
        assert "BIDIRECT" in KEYWORDS and "bidirect" in LEXED

    def test_from_ref(self):
        s = parse1("GO FROM $-.id OVER follow")
        assert isinstance(s.from_.ref, InputPropExpr)

    def test_yield_distinct(self):
        s = parse1("GO FROM 1 OVER e YIELD DISTINCT e._dst")
        assert s.yield_.distinct

    def test_negative_vid(self):
        s = parse1("GO FROM -7332961241633342590 OVER follow")
        # unary minus over literal
        from nebula_tpu.filter.expressions import UnaryExpr, ExprContext
        assert s.from_.vids[0].eval(ExprContext()) == -7332961241633342590


class TestPipesAndSets:
    def test_pipe(self):
        s = parse1("GO FROM 1 OVER e | GO FROM $-.id OVER e")
        assert isinstance(s, ast.PipedSentence)
        assert isinstance(s.left, ast.GoSentence)
        assert isinstance(s.right, ast.GoSentence)

    def test_pipe_chain_left_assoc(self):
        s = parse1("GO FROM 1 OVER e | GO FROM $- OVER e | GO FROM $- OVER e")
        assert isinstance(s, ast.PipedSentence)
        assert isinstance(s.left, ast.PipedSentence)

    def test_set_ops(self):
        s = parse1("GO FROM 1 OVER e UNION GO FROM 2 OVER e")
        assert isinstance(s, ast.SetSentence)
        assert s.op == ast.SetOpKind.UNION and s.distinct
        s2 = parse1("GO FROM 1 OVER e UNION ALL GO FROM 2 OVER e")
        assert not s2.distinct
        s3 = parse1("GO FROM 1 OVER e MINUS GO FROM 2 OVER e")
        assert s3.op == ast.SetOpKind.MINUS
        s4 = parse1("GO FROM 1 OVER e INTERSECT GO FROM 2 OVER e")
        assert s4.op == ast.SetOpKind.INTERSECT

    def test_count_star_parses_count_only(self):
        """COUNT(*) is sugar for the no-arg aggregate; the star must
        NOT generalize to other functions (SUM(*) has no meaning and
        silently counting rows under a sum label would be wrong)."""
        from nebula_tpu.filter.expressions import FunctionCallExpr
        s = parse1("GO FROM 1 OVER e | YIELD COUNT(*)")
        e = s.right.yield_.columns[0].expr
        assert isinstance(e, FunctionCallExpr)
        assert e.name.lower() == "count" and e.args == []
        from nebula_tpu.graph.parser.parser import GQLParser
        assert not GQLParser().parse(
            "GO FROM 1 OVER e | YIELD SUM(*)").ok()

    def test_assignment(self):
        s = parse1("$var = GO FROM 1 OVER e")
        assert isinstance(s, ast.AssignmentSentence)
        assert s.var == "var"
        assert isinstance(s.sentence, ast.GoSentence)

    def test_parenthesized_set(self):
        s = parse1("(GO FROM 1 OVER e UNION GO FROM 2 OVER e) | GO FROM $-.id OVER e")
        assert isinstance(s, ast.PipedSentence)
        assert isinstance(s.left, ast.SetSentence)


class TestTraverseOthers:
    def test_yield_sentence(self):
        s = parse1("YIELD 1+2 AS sum, hash(\"x\") AS h")
        assert isinstance(s, ast.YieldSentence)
        assert len(s.yield_.columns) == 2

    def test_order_by(self):
        s = parse1("GO FROM 1 OVER e | ORDER BY $-.age DESC, $-.name")
        ob = s.right
        assert isinstance(ob, ast.OrderBySentence)
        assert not ob.factors[0].ascending
        assert ob.factors[1].ascending

    def test_fetch_vertices(self):
        s = parse1("FETCH PROP ON player 1,2,3 YIELD player.name")
        assert isinstance(s, ast.FetchVerticesSentence)
        assert s.tag == "player"
        assert len(s.from_.vids) == 3

    def test_fetch_vertices_star(self):
        s = parse1("FETCH PROP ON * 1")
        assert s.tag == "*"

    def test_fetch_edges(self):
        s = parse1("FETCH PROP ON serve 100 -> 200 @1, 101 -> 201")
        assert isinstance(s, ast.FetchEdgesSentence)
        assert s.edge == "serve"
        assert s.keys[0].rank == 1 and s.keys[1].rank == 0

    def test_find_path(self):
        s = parse1("FIND SHORTEST PATH FROM 1 TO 2 OVER * UPTO 5 STEPS")
        assert isinstance(s, ast.FindPathSentence)
        assert s.shortest and s.over.is_all and s.upto.steps == 5
        s2 = parse1("FIND ALL PATH FROM 1 TO 2 OVER follow")
        assert not s2.shortest

    def test_find_legacy_stub(self):
        s = parse1("FIND name FROM 1")
        assert isinstance(s, ast.FindSentence)

    def test_match_stub(self):
        s = parse1("MATCH (v:player) RETURN v")
        assert isinstance(s, ast.MatchSentence)

    def test_match_basic_directions(self):
        s = parse1("MATCH (a:player)-[e:follow]->(b) "
                   "WHERE id(a) == 1 RETURN id(b)")
        assert s.a_var == "a" and s.e_label == "follow" \
            and s.b_var == "b" and not s.reverse
        s2 = parse1("MATCH (a)<-[e:follow]-(b:player) "
                    "WHERE id(a) == 3 RETURN id(b)")
        assert s2.reverse and s2.b_label == "player" \
            and s2.where_text and s2.return_text

    def test_match_var_length_bounds(self):
        s = parse1("MATCH (a)-[e:follow*3]->(b) "
                   "WHERE id(a) == 1 RETURN id(b)")
        assert (s.hop_min, s.hop_max) == (3, 3)
        # unspaced range lexes as two FLOATs; spaced as INT . . INT —
        # both must land the same bounds
        s2 = parse1("MATCH (a)-[e:follow*1..4]->(b) "
                    "WHERE id(a) == 1 RETURN id(b)")
        assert (s2.hop_min, s2.hop_max) == (1, 4)
        s3 = parse1("MATCH (a)-[e:follow*2 .. 5]->(b) "
                    "WHERE id(a) == 1 RETURN id(b)")
        assert (s3.hop_min, s3.hop_max) == (2, 5)
        s4 = parse1("MATCH (a)-[e:follow]->(b) "
                    "WHERE id(a) == 1 RETURN id(b)")
        assert (s4.hop_min, s4.hop_max) == (1, 1)

    def test_limit(self):
        s = parse1("GO FROM 1 OVER e | LIMIT 3, 10")
        assert s.right.offset == 3 and s.right.count == 10
        s2 = parse1("GO FROM 1 OVER e | LIMIT 10")
        assert s2.right.offset == 0 and s2.right.count == 10

    def test_group_by(self):
        s = parse1("GO FROM 1 OVER e YIELD e._dst AS d | "
                   "GROUP BY $-.d YIELD $-.d, count(1)")
        gb = s.right
        assert isinstance(gb, ast.GroupBySentence)


class TestMutate:
    def test_insert_vertex(self):
        s = parse1('INSERT VERTEX player(name, age) VALUES '
                   '100:("Tim Duncan", 42), 101:("Tony Parker", 36)')
        assert isinstance(s, ast.InsertVertexSentence)
        assert s.tags[0].name == "player"
        assert s.tags[0].props == ["name", "age"]
        assert len(s.rows) == 2
        assert s.rows[0].values[0].value == "Tim Duncan"

    def test_insert_multi_tag(self):
        s = parse1('INSERT VERTEX player(name), star(era) VALUES 1:("x", "90s")')
        assert len(s.tags) == 2

    def test_insert_edge(self):
        s = parse1('INSERT EDGE follow(degree) VALUES 100 -> 101@5:(95)')
        assert isinstance(s, ast.InsertEdgeSentence)
        assert s.edge == "follow"
        assert s.rows[0].rank == 5

    def test_insert_no_overwrite(self):
        s = parse1('INSERT EDGE NO OVERWRITE follow(degree) VALUES 1 -> 2:(1)')
        assert not s.overwritable

    def test_update_vertex(self):
        s = parse1('UPDATE VERTEX 100 SET age = $^.player.age + 1 '
                   'WHEN $^.player.age > 10 YIELD $^.player.age AS a')
        assert isinstance(s, ast.UpdateVertexSentence)
        assert s.items[0].prop == "age"
        assert s.where is not None and s.yield_ is not None

    def test_upsert_edge(self):
        s = parse1('UPSERT EDGE 1 -> 2@3 OF follow SET degree = 10')
        assert isinstance(s, ast.UpdateEdgeSentence)
        assert s.insertable and s.rank == 3 and s.edge == "follow"

    def test_delete(self):
        s = parse1("DELETE VERTEX 1, 2")
        assert isinstance(s, ast.DeleteVertexSentence)
        assert len(s.vids) == 2
        s2 = parse1("DELETE EDGE follow 1 -> 2, 3 -> 4@7")
        assert isinstance(s2, ast.DeleteEdgeSentence)
        assert s2.keys[1].rank == 7


class TestMaintain:
    def test_create_space(self):
        s = parse1("CREATE SPACE nba(partition_num=10, replica_factor=3)")
        assert isinstance(s, ast.CreateSpaceSentence)
        assert {p.name: p.value for p in s.props} == {
            "partition_num": 10, "replica_factor": 3}

    def test_create_space_if_not_exists(self):
        s = parse1("CREATE SPACE IF NOT EXISTS nba")
        assert s.if_not_exists

    def test_create_tag(self):
        s = parse1("CREATE TAG player(name string, age int, ppg double, "
                   "active bool, joined timestamp)")
        assert isinstance(s, ast.CreateTagSentence)
        assert [c.type_name for c in s.columns] == [
            "string", "int", "double", "bool", "timestamp"]

    def test_create_tag_ttl(self):
        s = parse1("CREATE TAG t(ts int) ttl_duration = 100, ttl_col = ts")
        assert {p.name: p.value for p in s.props} == {
            "ttl_duration": 100, "ttl_col": "ts"}

    def test_create_edge(self):
        s = parse1("CREATE EDGE follow(degree int)")
        assert isinstance(s, ast.CreateEdgeSentence)

    def test_alter(self):
        s = parse1("ALTER TAG player ADD (height double), DROP (age)")
        assert isinstance(s, ast.AlterTagSentence)
        assert s.items[0].op == "ADD"
        assert s.items[1].op == "DROP"
        s2 = parse1("ALTER EDGE e CHANGE (degree double)")
        assert s2.items[0].op == "CHANGE"

    def test_drop_describe(self):
        assert isinstance(parse1("DROP TAG player"), ast.DropTagSentence)
        assert isinstance(parse1("DROP EDGE IF EXISTS e"), ast.DropEdgeSentence)
        assert isinstance(parse1("DROP SPACE nba"), ast.DropSpaceSentence)
        assert isinstance(parse1("DESCRIBE TAG player"), ast.DescribeTagSentence)
        assert isinstance(parse1("DESC EDGE follow"), ast.DescribeEdgeSentence)
        assert isinstance(parse1("DESCRIBE SPACE nba"), ast.DescribeSpaceSentence)


class TestAdmin:
    def test_use(self):
        s = parse1("USE nba")
        assert isinstance(s, ast.UseSentence) and s.space == "nba"

    def test_show(self):
        assert parse1("SHOW SPACES").target == ast.ShowTarget.SPACES
        assert parse1("SHOW TAGS").target == ast.ShowTarget.TAGS
        assert parse1("SHOW EDGES").target == ast.ShowTarget.EDGES
        assert parse1("SHOW HOSTS").target == ast.ShowTarget.HOSTS
        assert parse1("SHOW USERS").target == ast.ShowTarget.USERS

    def test_hosts(self):
        s = parse1('ADD HOSTS "127.0.0.1:44500", "127.0.0.1:44501"')
        assert isinstance(s, ast.AddHostsSentence) and len(s.hosts) == 2
        s2 = parse1('REMOVE HOSTS "127.0.0.1:44500"')
        assert isinstance(s2, ast.RemoveHostsSentence)

    def test_configs(self):
        s = parse1("SHOW CONFIGS graph")
        assert s.action == "show" and s.module == "graph"
        s2 = parse1("GET CONFIGS storage:heartbeat_interval_secs")
        assert s2.action == "get" and s2.name == "heartbeat_interval_secs"
        s3 = parse1("UPDATE CONFIGS graph:v = 10")
        assert s3.action == "update" and s3.value is not None

    def test_balance(self):
        assert parse1("BALANCE DATA").target == "data"
        assert parse1("BALANCE LEADER").target == "leader"
        assert parse1("BALANCE DATA STOP").stop
        assert parse1("BALANCE DATA 12345").plan_id == 12345

    def test_users(self):
        s = parse1('CREATE USER alice WITH PASSWORD "pw"')
        assert isinstance(s, ast.CreateUserSentence)
        s2 = parse1('CHANGE PASSWORD alice FROM "a" TO "b"')
        assert s2.old_password == "a" and s2.new_password == "b"
        s3 = parse1("GRANT ROLE ADMIN ON nba TO alice")
        assert s3.role == "ADMIN"
        s4 = parse1("REVOKE ROLE GUEST ON nba FROM alice")
        assert isinstance(s4, ast.RevokeSentence)
        assert isinstance(parse1("DROP USER alice"), ast.DropUserSentence)

    def test_download_ingest(self):
        s = parse1('DOWNLOAD HDFS "hdfs://host:9000/path"')
        assert s.url == "hdfs://host:9000/path"
        assert isinstance(parse1("INGEST"), ast.IngestSentence)


class TestSequencesAndErrors:
    def test_sequential(self):
        r = P.parse("USE nba; GO FROM 1 OVER e; SHOW TAGS")
        assert r.ok() and len(r.value().sentences) == 3

    def test_trailing_semicolon(self):
        r = P.parse("USE nba;")
        assert r.ok() and len(r.value().sentences) == 1

    def test_empty(self):
        assert not P.parse("").ok()
        assert not P.parse(" ;;; ").ok()

    def test_syntax_errors(self):
        for bad in ("GO TO 3", "GO FROM OVER e", "INSERT VERTEX t() VALUES",
                    "CREATE TAG t(x notatype)", "FETCH PROP 1",
                    "GO FROM 1 OVER e YIELD", "@@@@"):
            st = parse_err(bad)
            assert "syntax" in st.to_string().lower() or True

    def test_comments(self):
        r = P.parse("USE nba -- comment here\n; # another\nSHOW TAGS // end")
        assert r.ok() and len(r.value().sentences) == 2

    def test_strings_escapes(self):
        s = parse1('YIELD "a\\"b\\n" AS x')
        assert s.yield_.columns[0].expr.value == 'a"b\n'

    def test_hex_int(self):
        s = parse1("YIELD 0xFF AS x")
        assert s.yield_.columns[0].expr.value == 255

    def test_case_insensitive_keywords(self):
        s = parse1("go from 1 over follow yield follow._dst")
        assert isinstance(s, ast.GoSentence)


class TestReferenceSyntaxParity:
    """Syntax forms harvested from the reference's own test suite
    (ParserTest.cpp / SchemaTest.cpp / graph tests)."""

    def _ok(self, q):
        from nebula_tpu.graph.parser import GQLParser
        r = GQLParser().parse(q)
        assert r.ok(), f"{q}: {r.status.msg}"
        return r.value()

    def _bad(self, q):
        from nebula_tpu.graph.parser import GQLParser
        assert not GQLParser().parse(q).ok(), q

    def test_comments(self):
        self._ok("CREATE TAG t1(x int) # trailing")
        self._ok("CREATE TAG t1(x int) -- trailing")
        self._ok("CREATE TAG t1(x int) // trailing")
        self._ok("CREATE TAG t1/* inline */(x int)")
        self._bad("CREATE TAG t1 /* unterminated (x int)")

    def test_unreserved_keywords_as_names(self):
        self._ok("CREATE TAG TAG1(space string, user int, balance double)")
        self._ok("GO FROM 1 OVER follow YIELD follow.space")

    def test_empty_and_trailing_comma_schemas(self):
        self._ok("CREATE TAG empty_tag()")
        self._ok("CREATE EDGE empty_edge()")
        self._ok("CREATE TAG t(x int, y string,)")
        self._bad("CREATE TAG t")            # parens required (parser.yy)
        self._bad("CREATE TAG t(x)")         # type required

    def test_show_variants(self):
        import nebula_tpu.graph.parser.ast as ast
        s = self._ok("SHOW CREATE TAG person").sentences[0]
        assert s.target == ast.ShowTarget.CREATE_TAG and s.name == "person"
        s = self._ok("SHOW CREATE EDGE e1").sentences[0]
        assert s.target == ast.ShowTarget.CREATE_EDGE
        s = self._ok("SHOW CREATE SPACE default_space").sentences[0]
        assert s.target == ast.ShowTarget.CREATE_SPACE
        s = self._ok("SHOW USER account").sentences[0]
        assert s.target == ast.ShowTarget.USER and s.name == "account"
        s = self._ok("SHOW ROLES IN spacename").sentences[0]
        assert s.target == ast.ShowTarget.ROLES and s.name == "spacename"
        s = self._ok("SHOW VARIABLES storage").sentences[0]
        assert s.kind == ast.Kind.CONFIG

    def test_variables_config_aliases(self):
        s = self._ok("UPDATE VARIABLES storage:k0=123").sentences[0]
        assert s.action == "update" and s.module == "storage"
        s = self._ok("GET VARIABLES storage:k1").sentences[0]
        assert s.action == "get"

    def test_bare_host_lists(self):
        s = self._ok("ADD HOSTS 127.0.0.1:1000, 127.0.0.1:9000").sentences[0]
        assert s.hosts == ["127.0.0.1:1000", "127.0.0.1:9000"]
        s = self._ok("REMOVE HOSTS 127.0.0.1:1000,").sentences[0]
        assert s.hosts == ["127.0.0.1:1000"]

    def test_nameless_delete_and_update_edge(self):
        s = self._ok("DELETE EDGE 123 -> 321,456 -> 654 "
                     "WHERE amount > 3.14").sentences[0]
        assert s.edge == "" and len(s.keys) == 2 and s.where is not None
        s = self._ok("UPDATE EDGE 12345 -> 54321 "
                     "SET amount=3.14,time=1537408527").sentences[0]
        assert s.edge == "" and len(s.items) == 2
        s = self._ok("UPDATE OR INSERT VERTEX 1 SET x=2").sentences[0]
        assert s.insertable

    def test_reference_negatives_still_fail(self):
        self._bad("ALTER EDGE woman ADD (col6)  ttl_duration = 200")
        self._bad("ALTER EDGE woman DROP (col6 int)  ttl_duration = 200")
        self._bad("CREATE TAG man(name string, age)")
        self._bad("YIELD $^[manager].name")
        self._bad("USE dumy tag_name")
