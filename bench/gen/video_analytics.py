"""Job ``video_analytics``: the ENTS (arXiv:2210.07842) Fig. 9
object-attribute-recognition DAG, its volumes and workloads jittered +-20%
per job, the source task pinned to the camera's node. A copy of
``repro.core.workloads.video_analytics_job``."""
from repro.core import JobGraph
from repro.core.graph import Task


def make(rng, source_node, *, input_size):
    def j(x):
        return float(x * rng.uniform(0.8, 1.2))

    tasks = [
        Task("source", 0.0, 0.0, pinned_node=source_node),
        Task("decode", j(4.0), 1.0),
        Task("detect", j(16.0), 2.0),
        Task("ped-attr-1", j(8.0), 1.5),
        Task("ped-attr-2", j(8.0), 1.5),
        Task("ped-reid", j(9.0), 1.5),
        Task("veh-attr-1", j(8.0), 1.5),
        Task("veh-attr-2", j(8.0), 1.5),
        Task("veh-reid", j(9.0), 1.5),
        Task("track", j(3.0), 1.0),
    ]
    edges = [
        (0, 1, j(input_size)),
        (1, 2, j(input_size * 0.8)),
        (2, 3, j(0.6)),
        (2, 4, j(0.6)),
        (2, 5, j(0.8)),
        (2, 6, j(0.6)),
        (2, 7, j(0.6)),
        (2, 8, j(0.8)),
        (3, 9, j(0.1)),
        (4, 9, j(0.1)),
        (5, 9, j(0.15)),
        (6, 9, j(0.1)),
        (7, 9, j(0.1)),
        (8, 9, j(0.15)),
    ]
    return JobGraph(tasks, edges, name="object-attr-recognition")
