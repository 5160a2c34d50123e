"""Share of the device's time spent in the operations whose scope path (the
``op_name`` the program's ``jax.named_scope``s and jax's own transforms
gave the instruction; benchmark/harness/xplane.py) matches the metric's
file:

    all_of    every one of these patterns is found in the path
    none_of   none of these is
    opcode    (optional) and this one is found in the operation's HLO text
    over      'busy': of the device's busy time (default); 'window': of the
              traced window, as ``collective_exposed_share`` counts
    requires  some operation of the trace carries this pattern, else the
              program has no such scopes and there is no number

Time is self time: a loop's body operations are taken out of the loop's own
event, and an operation the compiler made without a path (a copy inside a
loop's body) takes the path of the event it lies in."""
import re

from benchmark.harness import xplane


def read(params, facts, reduced):
    tr = xplane.load(reduced)
    if tr is None:
        return None
    all_of = [re.compile(p) for p in params.get('all_of', ())]
    none_of = [re.compile(p) for p in params.get('none_of', ())]
    opcode = re.compile(params['opcode']) if 'opcode' in params else None
    requires = re.compile(params['requires'])
    found, total, verdict = False, 0.0, {}
    for times in tr['self'].values():
        for name, t, path in times:
            if path not in verdict:
                found = found or bool(requires.search(path))
                verdict[path] = (all(p.search(path) for p in all_of)
                                 and not any(p.search(path) for p in none_of))
            if verdict[path] and (opcode is None or opcode.search(name)):
                total += t
    if not found:
        return None
    whole = tr['window_s' if params.get('over') == 'window' else 'busy_s']
    return 100.0 * total / 1e9 / tr['devices'] / whole
