from repro.serving.engine import (  # noqa: F401
    ADMISSION_MODES,
    EngineConfig,
    EngineStats,
    Request,
    ServeEngine,
)
from repro.serving.stream import (  # noqa: F401
    RequestStream,
    StreamConfig,
)
