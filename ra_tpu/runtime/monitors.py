"""The monitor table of one node: which servers watch which targets
(reference: ra_monitors). Both execution backends keep one a node: the
actor runtime (``runtime/node.RaNode.monitors``) and the batch backend
(``BatchCoordinator.monitors``)."""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Tuple

from ra_tpu.protocol import ServerId


class Monitors:
    """watcher server-id -> monitored targets (reference: ra_monitors)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # (kind, target) -> {(watcher_sid, component)}
        self._tab: Dict[Tuple[str, Any], set] = {}
        # watcher_sid -> {(kind, target)}: what ``forget`` walks
        self._by_watcher: Dict[ServerId, set] = {}

    def add(self, watcher: ServerId, kind: str, target: Any, component: str) -> None:
        with self._lock:
            self._tab.setdefault((kind, target), set()).add((watcher, component))
            self._by_watcher.setdefault(watcher, set()).add((kind, target))

    def remove(self, watcher: ServerId, kind: str, target: Any) -> None:
        with self._lock:
            self._drop(watcher, (kind, target))
            mine = self._by_watcher.get(watcher)
            if mine is not None:
                mine.discard((kind, target))
                if not mine:
                    del self._by_watcher[watcher]

    def forget(self, watcher: ServerId) -> int:
        """Drop every watch of ``watcher`` (it left the role that armed
        them); how many there were."""
        with self._lock:
            mine = self._by_watcher.pop(watcher, ())
            for key in mine:
                self._drop(watcher, key)
            return len(mine)

    def _drop(self, watcher: ServerId, key) -> None:
        s = self._tab.get(key)
        if s:
            left = {(w, c) for w, c in s if w != watcher}
            if left:
                self._tab[key] = left
            else:
                del self._tab[key]

    def watchers(self, kind: str, target: Any) -> List[Tuple[ServerId, str]]:
        return list(self._tab.get((kind, target), ()))
