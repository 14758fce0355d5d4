from .admission import (OpenLoopServer, QueryOutcome, ResultCache,
                        basket_key)
from .common import outcome_summary
from .rule_store import DEFAULT_TENANT, ArenaState, RuleStore
from .rules_engine import (Recommendation, RuleServeEngine, RuleServeRecord,
                           RULE_IMPLS)

__all__ = ["Recommendation", "RuleServeEngine", "RuleServeRecord",
           "RULE_IMPLS",
           "RuleStore", "ArenaState", "DEFAULT_TENANT",
           "OpenLoopServer", "QueryOutcome", "ResultCache", "basket_key",
           "outcome_summary"]
