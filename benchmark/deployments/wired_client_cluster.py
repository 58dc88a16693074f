"""``wired_cluster``'s three TCP-joined nodes and a fourth that holds no
replica: a ``RaNode`` built with ``tcp=True`` on ``127.0.0.1:<free
port>``, registered where ``ra_tpu.api`` looks (the process's node
registry; the three coordinators keep registries of their own, so
``api`` finds none of them). Every client call the generator makes
through ``api`` (``process_command``, ``consistent_query``, ``kv_get``)
to a leader ``(group, node)`` leaves through this node's
``TcpTransport`` as a call frame, and its reply comes back as a frame:
the ordinary case of a Ra caller on a connected node that is not a
member. No delay is injected.

What clients, references and metric readers see is ``batch_cluster``'s
``Cluster`` (inherited through ``wired_cluster``); ``snapshot()`` adds
the four nodes' client-path counters (family ``client``) and the two
hop histograms (family ``client_hops``).
"""

import errno
import os
import time

from benchmark import harness

_wc = harness.load_module("deployments", "wired_cluster")

HOPS = ("client_hop", "reply_hop")


class Cluster(_wc.Cluster):
    def __init__(self, config: dict, machine_factory, data_bases, say):
        from ra_tpu.runtime import tcp

        if not hasattr(tcp.TcpTransport, "send_call"):
            # (before anything is built: callers that cannot reach a
            # leader on another node would each sit out their timeout)
            raise SystemExit(
                "benchmark: wired_client_cluster needs a program whose "
                "api reaches a leader on another node "
                "(TcpTransport.send_call); this one's does not - no result")
        self.client_node = None
        super().__init__(config, machine_factory, data_bases, say)
        try:
            self._start_client_node(say)
        except BaseException:
            self.close()
            raise

    def _start_client_node(self, say) -> None:
        from ra_tpu.runtime.node import RaNode
        from ra_tpu.system import SystemConfig

        t0 = time.perf_counter()
        cfg = SystemConfig(name="bench_client",
                           data_dir=os.path.join(self.data_dir, "client"))
        for taken in range(9):
            port = _wc.free_port(self.HOST)
            try:
                self.client_node = RaNode(f"{self.HOST}:{port}", config=cfg,
                                          tcp=True)
                break
            except OSError as e:
                # (taken between the look and the bind: look again)
                if e.errno != errno.EADDRINUSE or taken == 8:
                    raise
        say("client_node", name=self.client_node.name,
            replicas_held=len(self.client_node.procs),
            start_s=time.perf_counter() - t0)

    def _transports(self):
        ts = [c.transport for c in self.coords]
        if self.client_node is not None:
            ts.append(self.client_node.transport)
        return ts

    def snapshot(self) -> dict:
        snap = super().snapshot()
        client, hops = {}, {}
        obs = self._obs
        for t in self._transports():
            for k, v in t.client.to_dict().items():
                client[k] = client.get(k, 0) + v
            for name in HOPS:
                h = obs.histograms().fetch(("client", t.node_name, name))
                if h is None:
                    continue
                got = hops.get(name)
                hops[name] = ((h.arr, h.n, h.total) if got is None else
                              (got[0] + h.arr, got[1] + h.n, got[2] + h.total))
        snap["client"] = client
        snap["client_hops"] = hops
        return snap

    def close(self) -> list:
        """The client node stops first (its logs lie in the data
        directory ``batch_cluster`` removes), then the three nodes; the
        names of every transport thread that outlived its ``stop()``."""
        left = []
        node, self.client_node = self.client_node, None
        if node is not None:
            node.stop()
            left = [t.name for t in node.transport.threads()]
        return super().close() + left
