# Hand-written stub (continuous.py defines no PipelineStage, so codegen
# skips it); kept in sync by tpulint rule TPU006 (stub-drift).
import threading
from typing import Any, Dict, List, Optional

class _Request:
    rid: int
    prompt: Any
    max_new: int
    temperature: float
    top_k: int
    top_p: float
    seed: int
    prefix_key: Optional[str]
    prefix_len: Optional[int]
    error: Optional[Exception]
    tokens: List[int]
    done: bool
    event: threading.Event
    submitted_at: float
    admitted_at: Optional[float]
    first_token_at: Optional[float]
    finished_at: Optional[float]
    span: Any
    cost_cls: Any
    cost_trace: Optional[str]
    session_id: str
    pre_emitted: List[int]
    journaled: int
    def timeline(self) -> Dict[str, object]: ...

def derived_page_size(cfg: Any, max_len: int) -> int: ...

class ContinuousDecoder:
    stats: Dict[str, int]
    def __init__(self, params: Dict, cfg: Any, *,
                 max_slots: int = ..., max_len: int = ...,
                 eos_id: Optional[int] = ...,
                 mesh: Optional[Any] = ...,
                 prefix_cache_size: int = ...,
                 steps_per_dispatch: int = ...,
                 pipeline_depth: int = ...,
                 prefill_ahead: int = ...,
                 draft_params: Optional[Dict] = ...,
                 draft_cfg: Optional[Any] = ...,
                 gamma: int = ...,
                 page_size: Optional[int] = ...,
                 prefill_chunk: int = ...,
                 kv_pages: Optional[int] = ...,
                 autotune: bool = ...,
                 defrag_threshold: Optional[int] = ...,
                 paged_attn: Optional[str] = ...,
                 kv_dtype: Optional[str] = ...,
                 quant_probe: int = ...,
                 slo_model: str = ...,
                 journal: Optional[Any] = ...) -> None: ...
    def submit(self, prompt_ids: Any, max_new_tokens: int = ..., *,
               temperature: float = ..., top_k: int = ...,
               top_p: float = ..., seed: int = ...,
               prefix_key: Optional[str] = ...,
               prefix_len: Optional[int] = ...,
               session_id: Optional[str] = ...,
               _journal_record: bool = ...) -> _Request: ...
    def result(self, req: _Request,
               timeout: Optional[float] = ...) -> List[int]: ...
    def session_result(self, req: _Request,
                       timeout: Optional[float] = ...) -> List[int]: ...
    def checkpoint_session(self, req: _Request, *,
                           export_kv: bool = ...) -> dict: ...
    def restore_session(self, sess: dict,
                        kv_blob: Optional[dict] = ...) -> _Request: ...
    def step(self) -> int: ...
    def flush(self) -> None: ...
    def cancel_all(self) -> None: ...
    def serve_forever(self, idle_sleep: float = ...,
                      max_failures: int = ...,
                      failure_backoff: float = ...) -> None: ...
    def start(self) -> threading.Thread: ...
    def stop(self) -> None: ...
