"""nGQL sentence AST.

Capability parity with the reference's Sentence tree
(/root/reference/src/parser/Sentence.h:20-58 — 38 kinds — plus
TraverseSentences.h, MutateSentences.h, MaintainSentences.h,
AdminSentences.h, UserSentences.h and Clauses.h). Nodes are plain
dataclasses; executors consume them (graph/executors/).
"""
from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

from ...filter.expressions import Expression


class Kind(enum.Enum):
    # traverse
    GO = "go"
    MATCH = "match"
    FIND = "find"
    FIND_PATH = "find_path"
    FETCH_VERTICES = "fetch_vertices"
    FETCH_EDGES = "fetch_edges"
    YIELD = "yield"
    ORDER_BY = "order_by"
    SET_OP = "set_op"
    PIPE = "pipe"
    ASSIGNMENT = "assignment"
    LIMIT = "limit"
    GROUP_BY = "group_by"
    # mutate
    INSERT_VERTEX = "insert_vertex"
    INSERT_EDGE = "insert_edge"
    UPDATE_VERTEX = "update_vertex"
    UPDATE_EDGE = "update_edge"
    DELETE_VERTEX = "delete_vertex"
    DELETE_EDGE = "delete_edge"
    # maintain
    CREATE_SPACE = "create_space"
    DROP_SPACE = "drop_space"
    DESCRIBE_SPACE = "describe_space"
    CREATE_TAG = "create_tag"
    CREATE_EDGE = "create_edge"
    ALTER_TAG = "alter_tag"
    ALTER_EDGE = "alter_edge"
    DROP_TAG = "drop_tag"
    DROP_EDGE = "drop_edge"
    DESCRIBE_TAG = "describe_tag"
    DESCRIBE_EDGE = "describe_edge"
    # admin
    USE = "use"
    SHOW = "show"
    ADD_HOSTS = "add_hosts"
    REMOVE_HOSTS = "remove_hosts"
    CONFIG = "config"
    BALANCE = "balance"
    DOWNLOAD = "download"
    INGEST = "ingest"
    KILL_QUERY = "kill_query"
    # users
    CREATE_USER = "create_user"
    ALTER_USER = "alter_user"
    DROP_USER = "drop_user"
    CHANGE_PASSWORD = "change_password"
    GRANT = "grant"
    REVOKE = "revoke"


class Sentence:
    kind: Kind


# ---------------------------------------------------------------- clauses
@dataclass
class StepClause:
    steps: int = 1
    upto: bool = False  # UPTO N STEPS


@dataclass
class FromClause:
    vids: Optional[List[Expression]] = None  # literal/expr vid list
    ref: Optional[Expression] = None         # $-.col or $var.col


@dataclass
class OverEdge:
    edge: str
    alias: Optional[str] = None


@dataclass
class OverClause:
    edges: List[OverEdge] = field(default_factory=list)
    is_all: bool = False        # OVER *
    reversely: bool = False     # every edge from its far end
    bidirect: bool = False      # every edge from either end

    def signs(self) -> Tuple[int, ...]:
        """The signs of each named edge type a step reads: the stored
        edge (+t), its reverse key (-t, mutate.py writes both), or both
        in the mirror's sort order."""
        if self.bidirect:
            return (-1, 1)
        return (-1,) if self.reversely else (1,)

    def resolve(self, sm, space: int) -> Dict[str, Tuple[int, ...]]:
        """alias (or edge name) -> the signed edge types it names, one
        but under BIDIRECT, where one name stands on both signs.
        KeyError(edge name) where the schema has no such edge."""
        if self.is_all:
            named = [(sm.edge_name(space, et), et)
                     for et in sm.all_edge_types(space)]
        else:
            named = []
            for oe in self.edges:
                r = sm.to_edge_type(space, oe.edge)
                if not r.ok():
                    raise KeyError(oe.edge)
                named.append((oe.alias or oe.edge, r.value()))
        signs = self.signs()
        return {name: tuple(sg * et for sg in signs)
                for name, et in named}

    def __str__(self) -> str:
        names = "*" if self.is_all else ", ".join(
            e.edge + (f" AS {e.alias}" if e.alias else "")
            for e in self.edges)
        word = " BIDIRECT" if self.bidirect else \
            " REVERSELY" if self.reversely else ""
        return f"OVER {names}{word}"


@dataclass
class WhereClause:
    filter: Expression = None


@dataclass
class YieldColumn:
    expr: Expression
    alias: Optional[str] = None


@dataclass
class YieldClause:
    columns: List[YieldColumn] = field(default_factory=list)
    distinct: bool = False


@dataclass
class OrderFactor:
    expr: Expression
    ascending: bool = True


# ---------------------------------------------------------------- traverse
@dataclass
class GoSentence(Sentence):
    kind = Kind.GO
    step: StepClause = field(default_factory=StepClause)
    from_: FromClause = field(default_factory=FromClause)
    over: OverClause = field(default_factory=OverClause)
    where: Optional[WhereClause] = None
    yield_: Optional[YieldClause] = None


@dataclass
class MatchSentence(Sentence):
    """MATCH — the basic single node-edge-node pattern
    ``MATCH (a[:tag])-[e:etype]->(b[:tag]) WHERE ... RETURN ...``
    (or the reverse-direction form ``(a)<-[e:etype]-(b)``) parses
    structurally and LOWERS onto the GO planner
    (executors/traverse.MatchExecutor); anything else keeps the raw
    text and errors E_UNSUPPORTED — which is already beyond the
    reference, whose MatchExecutor rejects everything
    (MatchExecutor.cpp:19-21)."""
    kind = Kind.MATCH
    raw: str = ""
    a_var: Optional[str] = None
    a_label: Optional[str] = None
    e_var: Optional[str] = None
    e_label: Optional[str] = None
    b_var: Optional[str] = None
    b_label: Optional[str] = None
    reverse: bool = False          # (a)<-[e]-(b): the edge runs b -> a
    hop_min: int = 1               # [e:t*N] -> (N, N); [e:t*1..N] ->
    hop_max: int = 1               # (1, N); plain [e:t] -> (1, 1)
    where_text: Optional[str] = None
    return_text: Optional[str] = None


@dataclass
class FindSentence(Sentence):
    kind = Kind.FIND
    props: List[str] = field(default_factory=list)
    from_: Optional[FromClause] = None
    where: Optional[WhereClause] = None


@dataclass
class FindPathSentence(Sentence):
    kind = Kind.FIND_PATH
    shortest: bool = True          # SHORTEST vs ALL
    from_: FromClause = field(default_factory=FromClause)
    to: FromClause = field(default_factory=FromClause)
    over: OverClause = field(default_factory=OverClause)
    upto: Optional[StepClause] = None


@dataclass
class FetchVerticesSentence(Sentence):
    kind = Kind.FETCH_VERTICES
    tag: str = "*"
    from_: FromClause = field(default_factory=FromClause)
    yield_: Optional[YieldClause] = None


@dataclass
class EdgeKeyRef:
    src: Expression
    dst: Expression
    rank: int = 0


@dataclass
class FetchEdgesSentence(Sentence):
    kind = Kind.FETCH_EDGES
    edge: str = ""
    keys: List[EdgeKeyRef] = field(default_factory=list)
    ref: Optional[Tuple[Expression, Expression]] = None  # ($-.src, $-.dst)
    yield_: Optional[YieldClause] = None


@dataclass
class YieldSentence(Sentence):
    kind = Kind.YIELD
    yield_: YieldClause = field(default_factory=YieldClause)
    where: Optional[WhereClause] = None


@dataclass
class OrderBySentence(Sentence):
    kind = Kind.ORDER_BY
    factors: List[OrderFactor] = field(default_factory=list)


@dataclass
class LimitSentence(Sentence):
    kind = Kind.LIMIT
    offset: int = 0
    count: int = -1


@dataclass
class GroupBySentence(Sentence):
    kind = Kind.GROUP_BY
    group_cols: List[YieldColumn] = field(default_factory=list)
    yield_: Optional[YieldClause] = None


class SetOpKind(enum.Enum):
    UNION = "union"
    INTERSECT = "intersect"
    MINUS = "minus"


@dataclass
class SetSentence(Sentence):
    kind = Kind.SET_OP
    op: SetOpKind = SetOpKind.UNION
    distinct: bool = True  # UNION dedups unless ALL
    left: Sentence = None
    right: Sentence = None


@dataclass
class PipedSentence(Sentence):
    kind = Kind.PIPE
    left: Sentence = None
    right: Sentence = None


@dataclass
class AssignmentSentence(Sentence):
    kind = Kind.ASSIGNMENT
    var: str = ""
    sentence: Sentence = None


# ---------------------------------------------------------------- mutate
@dataclass
class TagItem:
    name: str
    props: List[str]


@dataclass
class VertexRowItem:
    vid: Expression
    values: List[Expression]


@dataclass
class InsertVertexSentence(Sentence):
    kind = Kind.INSERT_VERTEX
    tags: List[TagItem] = field(default_factory=list)
    rows: List[VertexRowItem] = field(default_factory=list)
    overwritable: bool = True


@dataclass
class EdgeRowItem:
    src: Expression
    dst: Expression
    rank: int
    values: List[Expression]


@dataclass
class InsertEdgeSentence(Sentence):
    kind = Kind.INSERT_EDGE
    edge: str = ""
    props: List[str] = field(default_factory=list)
    rows: List[EdgeRowItem] = field(default_factory=list)
    overwritable: bool = True


@dataclass
class UpdateItem:
    prop: str
    value: Expression


@dataclass
class UpdateVertexSentence(Sentence):
    kind = Kind.UPDATE_VERTEX
    vid: Expression = None
    items: List[UpdateItem] = field(default_factory=list)
    where: Optional[WhereClause] = None
    yield_: Optional[YieldClause] = None
    insertable: bool = False


@dataclass
class UpdateEdgeSentence(Sentence):
    kind = Kind.UPDATE_EDGE
    src: Expression = None
    dst: Expression = None
    rank: int = 0
    edge: str = ""
    items: List[UpdateItem] = field(default_factory=list)
    where: Optional[WhereClause] = None
    yield_: Optional[YieldClause] = None
    insertable: bool = False


@dataclass
class DeleteVertexSentence(Sentence):
    kind = Kind.DELETE_VERTEX
    vids: List[Expression] = field(default_factory=list)
    where: Optional[WhereClause] = None


@dataclass
class DeleteEdgeSentence(Sentence):
    kind = Kind.DELETE_EDGE
    edge: str = ""
    keys: List[EdgeKeyRef] = field(default_factory=list)
    where: Optional[WhereClause] = None


# ---------------------------------------------------------------- maintain
@dataclass
class ColumnSpec:
    name: str
    type_name: str  # int/double/string/bool/timestamp
    default: object = None


@dataclass
class SchemaPropItem:
    name: str   # ttl_duration / ttl_col / partition_num / replica_factor
    value: object = None


@dataclass
class CreateSpaceSentence(Sentence):
    kind = Kind.CREATE_SPACE
    name: str = ""
    props: List[SchemaPropItem] = field(default_factory=list)
    if_not_exists: bool = False


@dataclass
class DropSpaceSentence(Sentence):
    kind = Kind.DROP_SPACE
    name: str = ""
    if_exists: bool = False


@dataclass
class DescribeSpaceSentence(Sentence):
    kind = Kind.DESCRIBE_SPACE
    name: str = ""


@dataclass
class CreateSchemaSentence(Sentence):
    """CREATE TAG / CREATE EDGE."""
    name: str = ""
    columns: List[ColumnSpec] = field(default_factory=list)
    props: List[SchemaPropItem] = field(default_factory=list)  # ttl
    if_not_exists: bool = False


class CreateTagSentence(CreateSchemaSentence):
    kind = Kind.CREATE_TAG


class CreateEdgeSentence(CreateSchemaSentence):
    kind = Kind.CREATE_EDGE


@dataclass
class AlterSchemaOptItem:
    op: str  # ADD / CHANGE / DROP
    columns: List[ColumnSpec] = field(default_factory=list)


@dataclass
class AlterSchemaSentence(Sentence):
    name: str = ""
    items: List[AlterSchemaOptItem] = field(default_factory=list)
    props: List[SchemaPropItem] = field(default_factory=list)


class AlterTagSentence(AlterSchemaSentence):
    kind = Kind.ALTER_TAG


class AlterEdgeSentence(AlterSchemaSentence):
    kind = Kind.ALTER_EDGE


@dataclass
class DropSchemaSentence(Sentence):
    name: str = ""
    if_exists: bool = False


class DropTagSentence(DropSchemaSentence):
    kind = Kind.DROP_TAG


class DropEdgeSentence(DropSchemaSentence):
    kind = Kind.DROP_EDGE


@dataclass
class DescribeSchemaSentence(Sentence):
    name: str = ""


class DescribeTagSentence(DescribeSchemaSentence):
    kind = Kind.DESCRIBE_TAG


class DescribeEdgeSentence(DescribeSchemaSentence):
    kind = Kind.DESCRIBE_EDGE


# ---------------------------------------------------------------- admin
@dataclass
class UseSentence(Sentence):
    kind = Kind.USE
    space: str = ""


class ShowTarget(enum.Enum):
    SPACES = "spaces"
    TAGS = "tags"
    EDGES = "edges"
    HOSTS = "hosts"
    PARTS = "parts"
    USERS = "users"
    USER = "user"                  # SHOW USER <account>
    ROLES = "roles"                # SHOW ROLES IN <space>
    CREATE_SPACE = "create space"  # SHOW CREATE SPACE <name>
    CREATE_TAG = "create tag"
    CREATE_EDGE = "create edge"
    CONFIGS = "configs"
    STATS = "stats"                # SHOW STATS: daemon + cluster rollup
    EVENTS = "events"              # SHOW EVENTS: cluster event journal
    QUERIES = "queries"            # SHOW QUERIES: live query registry
    TIMELINE = "timeline"          # SHOW TIMELINE: device flight recorder


@dataclass
class ShowSentence(Sentence):
    kind = Kind.SHOW
    target: ShowTarget = ShowTarget.SPACES
    module: Optional[str] = None  # SHOW CONFIGS graph
    name: Optional[str] = None    # SHOW USER/ROLES IN/CREATE * <name>
    count: Optional[int] = None   # SHOW TIMELINE <n>: row cap


@dataclass
class KillQuerySentence(Sentence):
    """KILL QUERY <id> — ends one live statement through the query
    registry (graph/query_registry.py); fans out across graphd
    replicas via metad when the id is not local."""
    kind = Kind.KILL_QUERY
    qid: int = 0


@dataclass
class HostsSentence(Sentence):
    hosts: List[str] = field(default_factory=list)


class AddHostsSentence(HostsSentence):
    kind = Kind.ADD_HOSTS


class RemoveHostsSentence(HostsSentence):
    kind = Kind.REMOVE_HOSTS


@dataclass
class ConfigSentence(Sentence):
    kind = Kind.CONFIG
    action: str = "show"  # show / get / update
    module: Optional[str] = None
    name: Optional[str] = None
    value: object = None


@dataclass
class BalanceSentence(Sentence):
    kind = Kind.BALANCE
    target: str = "data"  # data / leader
    stop: bool = False
    plan_id: Optional[int] = None


@dataclass
class DownloadSentence(Sentence):
    kind = Kind.DOWNLOAD
    url: str = ""


@dataclass
class IngestSentence(Sentence):
    kind = Kind.INGEST


# ---------------------------------------------------------------- users
@dataclass
class CreateUserSentence(Sentence):
    kind = Kind.CREATE_USER
    account: str = ""
    password: str = ""
    if_not_exists: bool = False


@dataclass
class AlterUserSentence(Sentence):
    kind = Kind.ALTER_USER
    account: str = ""
    password: str = ""


@dataclass
class DropUserSentence(Sentence):
    kind = Kind.DROP_USER
    account: str = ""
    if_exists: bool = False


@dataclass
class ChangePasswordSentence(Sentence):
    kind = Kind.CHANGE_PASSWORD
    account: str = ""
    old_password: Optional[str] = None
    new_password: str = ""


@dataclass
class GrantSentence(Sentence):
    kind = Kind.GRANT
    role: str = "GUEST"
    space: str = ""
    account: str = ""


@dataclass
class RevokeSentence(Sentence):
    kind = Kind.REVOKE
    role: str = "GUEST"
    space: str = ""
    account: str = ""


@dataclass
class SequentialSentences:
    sentences: List[Sentence] = field(default_factory=list)
    # leading PROFILE / EXPLAIN prefix (reference parser.yy explain
    # parity): PROFILE executes and attaches the span tree to the
    # response; EXPLAIN returns the executor plan without executing
    profile: bool = False
    explain: bool = False
    # PROFILE FORMAT=trace: attach the flight-recorder Chrome-trace
    # export (common/flight.py) instead of the raw span tree — host
    # spans + device tick rows, openable in Perfetto/chrome://tracing
    profile_format: Optional[str] = None
    # leading TIMEOUT <n> prefix: per-statement whole-request deadline
    # override in milliseconds (docs/admission.md); None = the
    # query_deadline_ms flag / client option applies
    timeout_ms: Optional[int] = None
