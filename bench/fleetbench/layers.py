"""Program names the trace reduction keys on.  ``PLACEMENT`` and
``COMPACTION`` match an op's name stack (its HLO ``op_name``, which
``trace.load`` reads from the program's HLO kept in the trace);
``COLLECTIVE`` matches the HLO instruction's opcode.  PERF.md, section 3,
says how each was confirmed on a chip trace."""

import re

#: ops under the jitted placement: ``fused_place`` (the Pallas kernel
#: with its boundary transposes) or ``fused_place_ref`` (the jnp oracle).
PLACEMENT = r"/jit\(fused_place(_ref)?\)(/|$)"
#: ops under the true branch of the segment scan's one ``lax.cond``:
#: compaction.  Anchored to the branch so that the scan's own loop
#: condition (``while/cond/...``) does not match.
COMPACTION = r"/cond/branch_1_fun(/|$)"
#: cross-chip reductions, by opcode: ``psum`` and ``pmax`` compile to
#: ``all-reduce`` instructions named after them (``psum.23``, ``pmax.7``),
#: or to an ``all-reduce-start``/``all-reduce-done`` pair where the
#: compiler makes them asynchronous.
COLLECTIVE = re.compile(r"^all-reduce(-start|-done)?$")
