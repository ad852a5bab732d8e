from .profiler import (Profiler, RecordEvent, dump_chrome_trace,  # noqa
                       is_recording)
