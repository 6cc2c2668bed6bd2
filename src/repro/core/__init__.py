"""The paper's primary contribution: the PTT (``hashset``) and PJTT
(``pjtt``) physical data structures, the planner and the executor whose
jitted steps run the SOM/ORM/OJM rules of an RML document over them, the
naive reference engine's join and dedup (``naive``), and the distributed
(shard_map) variants."""

from repro.core.executor import Engine, EngineConfig, KGResult, create_kg  # noqa: F401
