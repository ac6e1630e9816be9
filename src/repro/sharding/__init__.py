from repro.sharding.rules import (AxisRules, current_mesh, current_rules,
                                  logical_constraint, logical_sharding,
                                  make_mesh, param_sharding_tree, use_mesh)

__all__ = ["AxisRules", "current_mesh", "current_rules", "logical_constraint",
           "logical_sharding", "make_mesh", "param_sharding_tree", "use_mesh"]
