"""Reading a compiled program's HLO text in tests."""
import re

import jax


def ops_of_shape(hlo_text: str, opcodes: tuple[str, ...],
                 shape: tuple[int, ...]) -> list[str]:
    """The instructions, in every computation (fused ones too), whose
    opcode is one of ``opcodes`` and whose result has ``shape``."""
    pat = re.compile(r"= \w+\[%s\]\S* (?:%s)\(" % (
        ",".join(map(str, shape)), "|".join(map(re.escape, opcodes))))
    return [line.strip() for line in hlo_text.splitlines()
            if pat.search(line)]


def cache_aliases(hlo_text: str, params) -> list[str]:
    """The ``input_output_alias`` entries that return a decode step's
    donated ``{"k", "v"}`` cache (outputs 1 and 2) in the buffers of its
    parameters (the leaves after ``params``'s)."""
    first = len(jax.tree.leaves(params))
    want = [f"{{{i + 1}}}: ({first + i}, {{}}" for i in range(2)]
    header = hlo_text.splitlines()[0]
    return [w for w in want if w in header]
