"""join_inner's query, handed to the multi-tenant service: ``execute()``
submits the same ``LazyTable`` to ONE ``QueryService`` a process and waits
for its ticket, as a pipeline step does that hands its join to a shared
service and needs the answer before its next step. The service is made on
the first ``build``, every knob at its default (queue bound 256, quantum
1 MiB, no observability port, no statistics file); the traffic names the
tenant and whether a query runs analyzed."""
from cylon_tpu.service import QueryService

_service = []   # the process's one service, made on the first build


class Served:
    """What ``run.py`` calls on a query: ``explain()`` and ``execute()``."""

    def __init__(self, lazy, service, traffic):
        self._lazy, self._service, self._traffic = lazy, service, traffic

    def explain(self, *args, **kwargs):
        return self._lazy.explain(*args, **kwargs)

    def execute(self):
        ticket = self._service.submit(self._lazy,
                                      tenant=self._traffic["tenant"],
                                      analyze=self._traffic["analyze"])
        return ticket.result()


def build(plan, tables, traffic):
    if not _service:
        _service.append(QueryService(name="bench"))
    lazy = plan.scan(tables["left"]).join(plan.scan(tables["right"]),
                                          "inner", on=traffic["on"])
    return Served(lazy, _service[0], traffic)
