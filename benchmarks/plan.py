"""What a generator hands the client, and what comes back."""

from dataclasses import dataclass, field
from typing import Callable, Iterator, Optional


def png_ok(status, body):
    return status == 200 and body[:8] == b"\x89PNG\r\n\x1a\n"


@dataclass
class Req:
    kind: str                   # GetMap, Execute, ...
    path: str                   # "/ows?..." — the client adds the host
    valid: Callable[[int, bytes], bool]
    body: Optional[bytes] = None        # POST payload
    keep: bool = False          # keep the response body for the checks
    key: tuple = ()             # identity of what is asked for
    meta: dict = field(default_factory=dict)


@dataclass
class Plan:
    """A closed loop: `connections` clients take `reqs` in order, each
    waiting for its reply."""
    connections: int
    reqs: Iterator[Req]


@dataclass
class Result:
    req: Req
    sent: float                 # perf_counter when it was first sent
    done: float
    status: int
    ok: bool
    sheds: int                  # 503 + Retry-After seen, retried or not
    nbytes: int
    digest: bytes
    body: Optional[bytes] = None

    @property
    def latency_s(self):
        return self.done - self.sent
