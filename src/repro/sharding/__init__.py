from repro.sharding.logical import (RULES, batch_pspec, cache_shardings,
                                    input_shardings, mirror_pspec,
                                    opt_state_shardings, param_shardings,
                                    resolve_pspec)

__all__ = ['RULES', 'batch_pspec', 'cache_shardings', 'input_shardings',
           'mirror_pspec', 'opt_state_shardings', 'param_shardings',
           'resolve_pspec']
