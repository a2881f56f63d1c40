"""The program under test, as the benchmark drives it.

Builds the program's scenarios and MCS ladder from a configuration file
through its public constructors, so the file is what runs, and names the
receiver options the configuration states.
"""
from __future__ import annotations


def receiver_options(config: dict) -> dict:
    rx = config["receiver"]
    return {"fused": rx["fused"], "sic": rx["sic"],
            "mmse_smooth": rx["mmse_smooth"]}


def scenario_name(config: dict, rung: str) -> str:
    return f"bench.{config['name']}.{rung}"


def register(config: dict) -> str:
    """Register every rung of ``config`` as a program scenario and the
    rungs as one MCS ladder; returns the ladder's name."""
    from repro.phy import coding, ofdm, scenarios

    g, c = config["grid"], config["code"]
    grid = ofdm.GridConfig(
        n_subcarriers=g["n_subcarriers"], n_symbols=g["n_symbols"],
        pilot_stride=g["pilot_stride"],
        pilot_symbols=tuple(g["pilot_symbols"]), n_tx=g["n_tx"],
        n_rx=g["n_rx"], fft_size=g["fft_size"], n_taps=g["n_taps"],
        delay_spread=g["delay_spread"],
    )
    upd = config.get("user_power_db")
    names = []
    for r in config["rungs"]:
        code = coding.make_code(r["rate"], z=c["z"], k_b=c["k_b"],
                                col_degree=c["col_degree"],
                                seed=c["protograph_seed"])
        name = scenario_name(config, r["name"])
        scenarios.register_scenario(scenarios.LinkScenario(
            name, grid, r["modulation"], float(r["snr_db"]), code=code,
            user_power_db=tuple(upd) if upd else None,
        ), overwrite=True)
        names.append(name)
    ladder = f"bench.{config['name']}"
    scenarios.register_ladder(scenarios.MCSLadder(ladder, tuple(names)),
                              overwrite=True)
    return ladder
