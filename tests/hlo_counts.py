"""Counting the all-reduces a program asks for, from its text alone.

What a CPU run may establish is a count: the exposed milliseconds of a
collective are a chip's to say."""

import re


def lowered_all_reduces(text):
    """One ``(whiles, tag)`` per ``stablehlo.all_reduce`` of a lowered
    program (``lowered.as_text(debug_info=True)``): how many
    ``stablehlo.while`` regions enclose it, calls followed, and its
    ``grad_sync/<tag>`` scope (``parallel/overlap.py``), None without one."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    func, depth, whiles, open_op, ops, callers = None, 0, [], None, [], {}
    for line in text.splitlines():
        if m := re.search(r"func\.func \w+ @(\w+)", line):
            func = m[1]
        if m := re.search(r"call @(\w+)", line):
            callers.setdefault(m[1], []).append((func, len(whiles)))
        if "stablehlo.while" in line:
            whiles.append(depth)
        if '"stablehlo.all_reduce"' in line:
            open_op = depth
        depth += line.count("{") - line.count("}")
        if line.lstrip().startswith("}") and whiles and depth == whiles[-1]:
            whiles.pop()
        if open_op == depth:        # the line that closes the op carries its loc
            tag = re.search(r"grad_sync/(\w+)", names.get(
                re.search(r"loc\((#loc\d+)\)\s*$", line)[1], ""))
            ops.append((func, len(whiles), tag and tag[1]))
            open_op = None
    outer = lambda f: max((n + outer(g) for g, n in callers.get(f, [])), default=0)
    return [(n + outer(f), tag) for f, n, tag in ops]


def compiled_all_reduces(text):
    """The number of all-reduce instructions in a compiled program's HLO
    (``compiled.as_text()``); a variadic one's result type is a tuple."""
    return len(re.findall(
        r"^\s*(?:ROOT )?%?[\w.-]+ = (?:\(.*?\)|\S+) all-reduce(?:-start)?\(",
        text, re.M))
