"""``GO n STEPS ... | YIELD COUNT(*)``: the one row (count) of the
GO's rows, and no row where the GO returned none (a pipe with no input
yields nothing, on both of the program's backends).
semantics: {kind, steps}"""


def go_count(graph, start: int, steps: int) -> int:
    return int(graph.deg[graph.frontier(start, steps - 1)].sum())


def answer(graph, semantics: dict, key: int):
    n = go_count(graph, key, int(semantics["steps"]))
    return [(n,)] if n else []
