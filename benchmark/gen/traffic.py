"""The one traffic generator. A traffic mix is a JSON file of parameters
(`benchmark/traffic/<name>.json`); this module drives the control API from
it as a closed loop: a client keeps `outstanding_tasks` replicas created
and not yet ASSIGNED, and creates the next service whenever fewer remain.
"""
from __future__ import annotations

import threading
import time

from .services import ServiceStream, to_spec


class Client:
    """Creates services through `ControlAPI.create_service` on the leader,
    on one thread, and records each call."""

    def __init__(self, control, collector, cfg: dict, traffic: dict,
                 seed: int, prefix: str, block: int,
                 network_ids: dict | None = None):
        if traffic["loop"] != "closed":
            raise ValueError(f"traffic loop {traffic['loop']!r} is not "
                             f"generated")
        self._control = control
        self._collector = collector
        self._limit = int(traffic["outstanding_tasks"])
        self._network_ids = network_ids
        self.stream = ServiceStream(cfg, traffic["sizes"], seed, block,
                                    prefix)
        self.created: list[dict] = []    # one record per create call
        self.replicas = 0                # replicas asked for so far
        self.error: BaseException | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def _create(self, plain: dict) -> None:
        spec = to_spec(plain, self._network_ids)
        t_start = time.time()
        svc = self._control.create_service(spec)
        t_end = time.time()
        self._collector.admit(svc.id)
        self.replicas += plain["replicas"]
        self.created.append({"id": svc.id, "spec": plain,
                             "t_start": t_start, "t_end": t_end})

    def start(self) -> None:
        """Keep `outstanding_tasks` replicas waiting until stopped."""
        col = self._collector

        def room() -> bool:
            return self._stop.is_set() or \
                self.replicas - col.admitted_assigned < self._limit

        def body():
            try:
                while True:
                    col.wait_for(room, timeout=None)
                    if self._stop.is_set():
                        return
                    self._create(self.stream.next())
            except BaseException as exc:      # reported by the harness
                self.error = exc

        self._thread = threading.Thread(target=body, daemon=True,
                                        name="bench-client")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        col = self._collector
        with col.cond:
            col.cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=60.0)
