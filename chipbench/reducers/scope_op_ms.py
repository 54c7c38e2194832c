"""``scope_op_ms``: one kind of op inside one scope, in ms per ``per``.

``readers.scope_ms`` and ``readers.opcode_ms`` take one pattern each; this
takes both: the device self time of the ops whose scope matches ``scope``
AND whose instruction text matches ``text``, averaged over the chips,
divided by the run's count ``per`` (``steps``, ``batches``). It reads a
part of a scope that the program cannot name (an op the compiler makes out
of another: the ``-start`` of a row's transfer out of the host tier, a
relayout ``copy``) or that is not worth a name of its own. The text of an
op begins with its name (``%dynamic-slice-start.45 = (...) async-start(``):
anchor a name there, since an op's operands name other ops (a ``-done``
names its ``-start``).

    {"reducer": "scope_op_ms", "args": {"scope": "qt_lookup_cold",
     "text": "^%dynamic-slice-start", "per": "steps"}}

Nothing that matches both is None: the metric is left out of the line.
"""

import re

from chipbench import readers


def reduce(ctx, scope, text, per=None):
    in_scope, in_text = re.compile(scope), re.compile(text)
    s = ctx["trace"].seconds(lambda o: in_scope.search(o.scope) is not None
                             and in_text.search(o.text) is not None)
    n = readers.count_of(ctx, per)
    return None if s is None or n is None else 1e3 * s / n
