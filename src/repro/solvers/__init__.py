"""repro.solvers — applications that run on the transforms of a
``Croft3D`` plan.

  navier_stokes   RK4 substages of a pseudo-spectral DNS of
                  incompressible flow (Mortensen & Langtangen 2016)
"""
