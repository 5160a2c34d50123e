"""What a runner is handed: the cell's files, the arguments of the run, the
clock the set-up time is taken from, and a place for the lines a run prints
before its last."""
import dataclasses
import json
import time


@dataclasses.dataclass
class Context:
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    started: float              # time.perf_counter() at process start
    compiles: object            # device.CompileCounter
    out_dir: str
    control: dict = None        # overrides of the lower-precision control

    def log(self, phase, **fields):
        print(json.dumps({'phase': phase, 't_s': round(self.since_start(), 3),
                          **fields}), flush=True)

    def since_start(self):
        return time.perf_counter() - self.started


def check(name, value, limit, exact=False):
    """One number compared, beside its limit."""
    ok = (value == limit) if exact else (value <= limit)
    return {'name': name, 'value': value, 'limit': limit, 'ok': bool(ok)}
