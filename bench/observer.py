"""The benchmark's scheduler observer: wall-clock stamps at the moment
tokens reach the host.

``SlotScheduler`` calls the ``on_*`` hooks of the object passed as its
``telemetry``.  ``on_tokens`` fires right after a session's tokens are
delivered: inside admission for the first token (the prefill's sample,
already on the host) and after each decode chunk's one host sync for
the rest.  The stamps here are ``time.perf_counter`` at those moments;
the scheduler's own chunk-unit clock is not used.

Each delivery is also kept as ``(time, sid, first_index, n)``: the
positions the model fed to produce them, from which the configuration
counts the work a window required.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class Record:
    """What one request experienced, in ``perf_counter`` seconds."""
    sid: int
    request: object                  # traffic_common.Request
    prompt_len: int
    sent: float                      # due (open loop) or sent (closed)
    first: Optional[float] = None    # first token on the host
    last: Optional[float] = None     # latest token on the host
    n: int = 0                       # tokens delivered
    done: Optional[float] = None     # retired
    session: object = None


class Observer:
    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.records: Dict[int, Record] = {}
        # (time, sid, index of the first new token, n new tokens)
        self.deliveries: List[Tuple[float, int, int, int]] = []
        # (time, sid, prompt_len) per admission
        self.admissions: List[Tuple[float, int, int]] = []
        self.retired: List[Record] = []

    def track(self, session, request, sent: float) -> Record:
        rec = Record(session.sid, request, len(session.prompt), sent,
                     session=session)
        self.records[session.sid] = rec
        return rec

    # -- hooks the scheduler calls ------------------------------------------
    def on_submit(self, session, clock: int) -> None:
        pass

    def on_admit(self, session, clock: int, source: str) -> None:
        rec = self.records.get(session.sid)
        if rec is None:
            return
        self.admissions.append((self.clock(), session.sid, rec.prompt_len))

    def on_spill(self, session, clock: int) -> None:
        pass

    def on_spec(self, session, drafted: int, accepted: int) -> None:
        pass

    def on_tick(self, clock, n_active, n_pending, free_pages, total_pages
                ) -> None:
        pass

    def on_tokens(self, session, n_new: int, clock: int,
                  compiled: bool) -> None:
        if n_new <= 0:
            return
        t = self.clock()
        rec = self.records.get(session.sid)
        if rec is None:
            return
        if rec.first is None:
            rec.first = t
        rec.last = t
        self.deliveries.append((t, session.sid, len(session.tokens) - n_new,
                                n_new))
        rec.n += n_new

    def on_retire(self, session, clock: int) -> None:
        rec = self.records.get(session.sid)
        if rec is None:
            return
        rec.done = self.clock()
        self.retired.append(rec)

    # -- reading ---------------------------------------------------------------
    def drain_retired(self) -> List[Record]:
        out, self.retired = self.retired, []
        return out
