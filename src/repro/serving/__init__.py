from repro.serving.executors import (  # noqa: F401
    Executor, ExecutorCache, ExecutorKey)
from repro.serving.scheduler import (  # noqa: F401
    ManualClock, MicroBatchScheduler, ResultCache, Request, form_batches)
from repro.serving.sharding import (  # noqa: F401
    DeviceHealth, ShardSpec, shard_width, sharded_forward)
from repro.serving.telemetry import Telemetry  # noqa: F401
from repro.serving.vision import VisionEngine, VisionServeConfig  # noqa: F401
