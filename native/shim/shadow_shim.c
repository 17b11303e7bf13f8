/* shadow_shim: LD_PRELOADed interposition runtime for managed plugins.
 *
 * Rebuild of the reference's in-plugin shim (src/lib/shim/): co-opts a real,
 * unmodified Linux binary into the discrete-event simulation by interposing
 * the libc API surface the simulation owns:
 *
 *   - time (clock_gettime/gettimeofday/time) is serviced *locally* from the
 *     shared-memory sim clock, no channel hop (shim/shim_sys.c:24-37);
 *   - sleeping and socket I/O (UDP datagrams and TCP streams) round-trip to
 *     the manager over a pair of futex-word channels in shared memory (the
 *     IPCData equivalent, shadow-shim-helper-rs/src/ipc.rs:14);
 *   - readiness (poll/select/epoll) over simulated fds is evaluated by the
 *     manager against the simulated transport state (SHIM_OP_POLL);
 *   - getrandom / /dev/urandom-free entropy is deterministic splitmix64
 *     keyed per process (preload-openssl/src/rng.c's determinism goal).
 *
 * Simulated sockets occupy REAL fd numbers: each is backed by a reserved
 * kernel fd (dup of /dev/null), so simulated fds never collide with the
 * plugin's own files and stay below FD_SETSIZE — the LD_PRELOAD analog of
 * the reference owning the plugin's descriptor table
 * (descriptor/descriptor_table.rs).
 *
 * Interposition is layered (the reference's exact discipline,
 * preload-libc/: "faster than seccomp"):
 *
 *   1. symbol-level LD_PRELOAD wrappers — the fast path for PLT calls;
 *   2. vDSO patching for glibc-internal time reads;
 *   3. a raw-syscall backstop for everything else: syscall-user-dispatch
 *      (PR_SET_SYSCALL_USER_DISPATCH, the mechanism the reference's own
 *      comments recommend migrating to, shim_seccomp.c "Better yet...")
 *      dispatches EVERY syscall issued outside this .so's text into the
 *      SIGSYS handler, which routes simulation-owned calls (sockets,
 *      readiness, futex, time, fork) through the same wrapper logic and
 *      re-executes the rest natively.  Unlike a seccomp filter, SUD is
 *      reset by execve, so exec'd images re-install cleanly with no
 *      stale-filter generation to dodge.  On kernels without SUD
 *      (< 5.11) a narrow seccomp filter covering the time/sleep/entropy
 *      set is installed instead (the round-1 behavior).
 *
 * Static binaries are rejected by the manager, as in the reference
 * (src/test/static-bin).
 */
#define _GNU_SOURCE
#include <arpa/inet.h>
#include <dlfcn.h>
#include <errno.h>
#include <fcntl.h>
#include <limits.h>
#include <linux/futex.h>
#include <netinet/in.h>
#include <poll.h>
#include <signal.h>
#include <stdarg.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <sys/epoll.h>
#include <sys/ioctl.h>
#include <sys/mman.h>
#include <sys/random.h>
#include <sys/select.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/syscall.h>
#include <sys/resource.h>
#include <sys/time.h>
#include <sys/timerfd.h>
#include <sys/uio.h>
#include <sys/utsname.h>
#include <sys/wait.h>
#include <time.h>
#include <unistd.h>

#include "../include/shadow_shim_abi.h"

#include <pthread.h>
#include <setjmp.h>
#include <semaphore.h>

#define SHIM_MAX_FDS 4096

static shim_shmem *g_shm = NULL;
/* Secondary threads exchange on their OWN channel (one per thread, exactly
 * the reference's one-IPCData-per-ManagedThread, managed_thread.rs:355);
 * the main thread and pre-thread code use g_shm. */
static __thread shim_shmem *t_shm = NULL;
static __thread int64_t t_vtid = 0; /* 0 = main thread */
static __thread int t_exit_sent = 0;
/* raw-clone adoption (Go-runtime-style threads): the boot block of an
 * adopted thread (its ctid word and retirement jump buffer live there),
 * and the interrupted context of the CURRENT dispatch frame (the handler
 * CAN nest — SA_NODEFER — so dispatch saves and restores it) */
static __thread void *t_boot = NULL;
static __thread void *t_cur_uc = NULL;

static shim_shmem *cur_shm(void) { return t_shm ? t_shm : g_shm; }
static int g_ready = 0;
/* exit code captured by the exit wrapper so the destructor's farewell can
 * report it (fork children are the PLUGIN's OS children; the manager
 * cannot waitpid them itself) */
static int g_exit_code = 0;

/* per-fd shim state: kind + O_NONBLOCK, indexed by the real fd number */
enum { VK_NONE = 0, VK_SOCKET = 1, VK_NETLINK = 2 };
static uint8_t vfd_kind[SHIM_MAX_FDS];
static uint8_t vfd_nonblock[SHIM_MAX_FDS];
static uint8_t vfd_stream[SHIM_MAX_FDS]; /* SOCK_STREAM (vs SOCK_DGRAM) */
static uint8_t vfd_listening[SHIM_MAX_FDS];

/* per-epfd registration of simulated fds (real fds still ride the real
 * epoll object; mixing both in one wait services the simulated side) */
typedef struct {
    int fd;
    uint32_t events;
    uint64_t data;
} epoll_reg;
#define EPOLL_MAX_REGS 1024
static epoll_reg *epoll_regs[SHIM_MAX_FDS]; /* array per epfd, lazy alloc */
static int epoll_nregs[SHIM_MAX_FDS];
static uint8_t epoll_has_real[SHIM_MAX_FDS]; /* real fds also registered */

/* a closing fd leaves every epoll interest list (Linux auto-deregisters);
 * a closing epfd drops its whole registration table */
static void epoll_forget_fd(int fd) {
    if (fd < 0 || fd >= SHIM_MAX_FDS) return;
    epoll_nregs[fd] = 0;
    epoll_has_real[fd] = 0;
    for (int ep = 0; ep < SHIM_MAX_FDS; ep++) {
        epoll_reg *regs = epoll_regs[ep];
        int n = epoll_nregs[ep];
        for (int i = 0; i < n; i++) {
            if (regs[i].fd == fd) {
                regs[i] = regs[n - 1];
                epoll_nregs[ep] = --n;
                i--;
            }
        }
    }
}

/* real libc entry points (resolved once; interposed wrappers fall through
 * for fds we don't own) */
static int (*real_socket)(int, int, int);
static int (*real_bind)(int, const struct sockaddr *, socklen_t);
static int (*real_connect)(int, const struct sockaddr *, socklen_t);
static int (*real_listen)(int, int);
static int (*real_accept4)(int, struct sockaddr *, socklen_t *, int);
static ssize_t (*real_sendto)(int, const void *, size_t, int,
                              const struct sockaddr *, socklen_t);
static ssize_t (*real_recvfrom)(int, void *, size_t, int, struct sockaddr *,
                                socklen_t *);
static int (*real_close)(int);
static int (*real_shutdown)(int, int);
static int (*real_getsockname)(int, struct sockaddr *, socklen_t *);
static int (*real_getpeername)(int, struct sockaddr *, socklen_t *);
static int (*real_setsockopt)(int, int, int, const void *, socklen_t);
static int (*real_getsockopt)(int, int, int, void *, socklen_t *);
static ssize_t (*real_read)(int, void *, size_t);
static ssize_t (*real_write)(int, const void *, size_t);
static int (*real_fcntl)(int, int, ...);
static int (*real_ioctl)(int, unsigned long, ...);
static int (*real_poll)(struct pollfd *, nfds_t, int);
static int (*real_select)(int, fd_set *, fd_set *, fd_set *, struct timeval *);
static int (*real_epoll_ctl)(int, int, int, struct epoll_event *);
static int (*real_epoll_wait)(int, struct epoll_event *, int, int);

/* Every fallback the wrappers use is a raw syscall issued from THIS
 * object's text, never a dlsym'd libc function: (a) the backstop's allowed
 * region is this .so's text, so shim-internal syscalls never trap; (b) a
 * dlsym'd fallback reached from the SIGSYS handler would re-enter libc,
 * whose syscall instruction traps again — unbounded recursion.  These are
 * thin kernel wrappers with libc return conventions (-1 + errno). */
static long shim_raw_syscall6(long nr, long a1, long a2, long a3, long a4,
                              long a5, long a6);

static long raw_ret(long r) {
    if (r < 0) {
        errno = (int)-r;
        return -1;
    }
    return r;
}

#define RAW1(rt, name, nr, t1)                                               \
    static rt raw_##name(t1 a) {                                             \
        return (rt)raw_ret(shim_raw_syscall6(nr, (long)a, 0, 0, 0, 0, 0));   \
    }
#define RAW2(rt, name, nr, t1, t2)                                           \
    static rt raw_##name(t1 a, t2 b) {                                       \
        return (rt)raw_ret(                                                  \
            shim_raw_syscall6(nr, (long)a, (long)b, 0, 0, 0, 0));            \
    }
#define RAW3(rt, name, nr, t1, t2, t3)                                       \
    static rt raw_##name(t1 a, t2 b, t3 c) {                                 \
        return (rt)raw_ret(                                                  \
            shim_raw_syscall6(nr, (long)a, (long)b, (long)c, 0, 0, 0));      \
    }
#define RAW4(rt, name, nr, t1, t2, t3, t4)                                   \
    static rt raw_##name(t1 a, t2 b, t3 c, t4 d) {                           \
        return (rt)raw_ret(shim_raw_syscall6(nr, (long)a, (long)b, (long)c,  \
                                             (long)d, 0, 0));                \
    }
#define RAW5(rt, name, nr, t1, t2, t3, t4, t5)                               \
    static rt raw_##name(t1 a, t2 b, t3 c, t4 d, t5 e) {                     \
        return (rt)raw_ret(shim_raw_syscall6(nr, (long)a, (long)b, (long)c,  \
                                             (long)d, (long)e, 0));          \
    }
#define RAW6_(rt, name, nr, t1, t2, t3, t4, t5, t6)                          \
    static rt raw_##name(t1 a, t2 b, t3 c, t4 d, t5 e, t6 f) {               \
        return (rt)raw_ret(shim_raw_syscall6(nr, (long)a, (long)b, (long)c,  \
                                             (long)d, (long)e, (long)f));    \
    }

RAW3(int, socket, SYS_socket, int, int, int)
RAW3(int, bind, SYS_bind, int, const struct sockaddr *, socklen_t)
RAW3(int, connect, SYS_connect, int, const struct sockaddr *, socklen_t)
RAW2(int, listen, SYS_listen, int, int)
RAW4(int, accept4, SYS_accept4, int, struct sockaddr *, socklen_t *, int)
RAW6_(ssize_t, sendto, SYS_sendto, int, const void *, size_t, int,
      const struct sockaddr *, socklen_t)
RAW6_(ssize_t, recvfrom, SYS_recvfrom, int, void *, size_t, int,
      struct sockaddr *, socklen_t *)
RAW1(int, close, SYS_close, int)
RAW2(int, shutdown, SYS_shutdown, int, int)
RAW3(int, getsockname, SYS_getsockname, int, struct sockaddr *, socklen_t *)
RAW3(int, getpeername, SYS_getpeername, int, struct sockaddr *, socklen_t *)
RAW5(int, setsockopt, SYS_setsockopt, int, int, int, const void *, socklen_t)
RAW5(int, getsockopt, SYS_getsockopt, int, int, int, void *, socklen_t *)
RAW3(ssize_t, read, SYS_read, int, void *, size_t)
RAW3(ssize_t, write, SYS_write, int, const void *, size_t)
RAW3(int, poll_, SYS_poll, struct pollfd *, nfds_t, int)
RAW5(int, select, SYS_select, int, fd_set *, fd_set *, fd_set *,
     struct timeval *)
RAW4(int, epoll_ctl, SYS_epoll_ctl, int, int, int, struct epoll_event *)
RAW4(int, epoll_wait, SYS_epoll_wait, int, struct epoll_event *, int, int)
RAW3(ssize_t, recvmsg, SYS_recvmsg, int, struct msghdr *, int)
RAW3(ssize_t, sendmsg, SYS_sendmsg, int, const struct msghdr *, int)
RAW3(ssize_t, readv, SYS_readv, int, const struct iovec *, int)
RAW3(ssize_t, writev, SYS_writev, int, const struct iovec *, int)
RAW1(int, dup, SYS_dup, int)
RAW2(int, dup2_, SYS_dup2, int, int)
RAW3(int, dup3_, SYS_dup3, int, int, int)
RAW2(int, timerfd_create, SYS_timerfd_create, int, int)
RAW4(int, timerfd_settime, SYS_timerfd_settime, int, int,
     const struct itimerspec *, struct itimerspec *)
RAW2(int, timerfd_gettime, SYS_timerfd_gettime, int, struct itimerspec *)
RAW2(int, eventfd2, SYS_eventfd2, unsigned int, int)
RAW1(int, uname_, SYS_uname, struct utsname *)

static int raw_fcntl(int fd, int cmd, ...) {
    va_list ap;
    va_start(ap, cmd);
    long arg = va_arg(ap, long);
    va_end(ap);
    return (int)raw_ret(shim_raw_syscall6(SYS_fcntl, fd, cmd, arg, 0, 0, 0));
}

static int raw_ioctl(int fd, unsigned long req, ...) {
    va_list ap;
    va_start(ap, req);
    long arg = va_arg(ap, long);
    va_end(ap);
    return (int)raw_ret(
        shim_raw_syscall6(SYS_ioctl, fd, (long)req, arg, 0, 0, 0));
}

static void resolve_reals(void) {
    if (real_socket) return;
    real_socket = raw_socket;
    real_bind = raw_bind;
    real_connect = raw_connect;
    real_listen = raw_listen;
    real_accept4 = raw_accept4;
    real_sendto = raw_sendto;
    real_recvfrom = raw_recvfrom;
    real_close = raw_close;
    real_shutdown = raw_shutdown;
    real_getsockname = raw_getsockname;
    real_getpeername = raw_getpeername;
    real_setsockopt = raw_setsockopt;
    real_getsockopt = raw_getsockopt;
    real_read = raw_read;
    real_write = raw_write;
    real_fcntl = raw_fcntl;
    real_ioctl = raw_ioctl;
    real_poll = raw_poll_;
    real_select = raw_select;
    real_epoll_ctl = raw_epoll_ctl;
    real_epoll_wait = raw_epoll_wait;
}

/* ---------------------------------------------------------------- futex */

static void futex_wait(uint32_t *addr, uint32_t expected) {
    shim_raw_syscall6(SYS_futex, (long)addr, FUTEX_WAIT, expected, 0, 0, 0);
}

static void futex_wake(uint32_t *addr) {
    shim_raw_syscall6(SYS_futex, (long)addr, FUTEX_WAKE, 1, 0, 0, 0);
}

static void msg_publish(shim_msg *m) {
    __atomic_store_n(&m->turn, 1, __ATOMIC_RELEASE);
    futex_wake(&m->turn);
}

static void msg_await(shim_msg *m) {
    while (__atomic_load_n(&m->turn, __ATOMIC_ACQUIRE) == 0)
        futex_wait(&m->turn, 0);
    __atomic_store_n(&m->turn, 0, __ATOMIC_RELEASE);
}

/* Synchronous call: fill to_shadow, wake manager, block for the reply.
 * The protocol strictly alternates, exactly like the reference's
 * ManagedThread::continue_plugin loop (managed_thread.rs:434-472).
 *
 * Handler-reentrancy guard: a handler running mid-exchange (e.g. bash's
 * SIGCHLD reaper calling waitpid) would issue a REENTRANT shim_call and
 * corrupt the alternation.  All signals except the termination/fault set
 * are masked for the duration — deferred handlers run between calls,
 * where their own calls are safe; SIGTERM/SIGINT/SIGQUIT stay deliverable
 * so a shutdown_signal can still kill a parked plugin. */
static int64_t shim_call(uint32_t op, const int64_t args[6], const void *out,
                         uint32_t out_len, void *in, uint32_t *in_len,
                         int64_t reply_args[6]) {
    /* mask everything except termination/fault signals: handler
     * reentrancy is excluded wholesale, while a shutdown_signal can still
     * kill a parked plugin and faults stay synchronous.  Raw
     * rt_sigprocmask on the 64-bit kernel sigset — libc's sigprocmask
     * issues its syscall from libc text, which the dispatch backstop
     * traps; the restore (with SIGSYS then blocked) would turn that trap
     * into a forced-SIGSYS kill. */
    /* Block EVERYTHING except the fault set and SIGSYS during the
     * exchange: an app handler running while this thread is parked would
     * issue a REENTRANT shim_call and corrupt the strict alternation.
     * Deferred handlers run at the mask restore below — and the manager
     * completes a parked interruptible call with -EINTR when it delivers
     * a handled signal, so handlers are never starved by a long park.
     * SIGSYS stays open (dispatch infrastructure: a handler inheriting a
     * blocked-SIGSYS context would be force-killed on its first
     * interposed call); faults stay synchronous. */
    static const uint64_t sig_blk =
        ~((1ull << (SIGSEGV - 1)) | (1ull << (SIGBUS - 1)) |
          (1ull << (SIGILL - 1)) | (1ull << (SIGFPE - 1)) |
          (1ull << (SIGABRT - 1)) | (1ull << (SIGSYS - 1)));
    uint64_t sig_old = 0;
    shim_raw_syscall6(SYS_rt_sigprocmask, SIG_SETMASK, (long)&sig_blk,
                      (long)&sig_old, 8, 0, 0);
    shim_shmem *shm = cur_shm();
    shim_msg *tx = &shm->to_shadow;
    shim_msg *rx = &shm->to_shim;
    tx->op = op;
    for (int i = 0; i < 6; i++) tx->args[i] = args ? args[i] : 0;
    if (out_len > SHIM_PAYLOAD_MAX) out_len = SHIM_PAYLOAD_MAX;
    if (out && out_len) memcpy(tx->payload, out, out_len);
    tx->payload_len = out_len;
    msg_publish(tx);
    msg_await(rx);
    if (reply_args)
        for (int i = 0; i < 6; i++) reply_args[i] = rx->args[i];
    if (in && in_len) {
        uint32_t n = rx->payload_len < *in_len ? rx->payload_len : *in_len;
        memcpy(in, rx->payload, n);
        *in_len = n;
    }
    int64_t ret = rx->ret;
    shim_raw_syscall6(SYS_rt_sigprocmask, SIG_SETMASK, (long)&sig_old, 0, 8,
                      0, 0);
    return ret;
}

/* return-value helper: negative ret carries -errno */
static int64_t ret_errno(int64_t ret) {
    if (ret < 0) {
        errno = (int)-ret;
        return -1;
    }
    return ret;
}

/* ------------------------------------------------------------ init/exit */

static void shim_abort(const char *why) {
    const char *msg = "shadow_shim: fatal: ";
    (void)!write(2, msg, strlen(msg));
    (void)!write(2, why, strlen(why));
    (void)!write(2, "\n", 1);
    _exit(127);
}

static void shim_warn(const char *what) {
    const char *msg = "shadow_shim: warning: ";
    (void)!real_write(2, msg, strlen(msg));
    (void)!real_write(2, what, strlen(what));
    (void)!real_write(2, "\n", 1);
}

static shim_shmem *shim_map(const char *path) {
    int fd = open(path, O_RDWR);
    if (fd < 0) shim_abort("cannot open shim channel file");
    struct stat st;
    if (fstat(fd, &st) != 0 || (size_t)st.st_size < sizeof(shim_shmem))
        shim_abort("shm too small");
    shim_shmem *shm = mmap(NULL, sizeof(shim_shmem), PROT_READ | PROT_WRITE,
                           MAP_SHARED, fd, 0);
    real_close(fd);
    if (shm == MAP_FAILED) shim_abort("mmap failed");
    if (shm->magic != SHIM_ABI_MAGIC || shm->abi_size != sizeof(shim_shmem))
        shim_abort("ABI mismatch between shim and manager");
    return shm;
}

static void shim_attach(const char *path) { g_shm = shim_map(path); }

/* --------------------------------------- interposition backstops.
 * LD_PRELOAD only catches PLT calls; two further layers close the gaps the
 * reference closes (shim/shim_seccomp.c, shim/patch_vdso.c):
 *
 *   1. vDSO patching: glibc-internal time reads and runtime-direct vDSO
 *      calls never hit a syscall at all.  The vDSO entry points are
 *      overwritten with jumps into sim-clock implementations.
 *   2. seccomp SIGSYS trap: raw `syscall(...)` invocations of the time/
 *      sleep/entropy set are trapped and emulated; anything else raw runs
 *      natively.  The BPF filter allows syscalls issued from THIS .so's
 *      text segment (instruction-pointer range), so the shim services
 *      traps with its own raw-syscall helper without re-trapping —
 *      the reference's allow-own-text discipline (shim_seccomp.c:36-70).
 */

static uint64_t sim_now_ns(void);      /* defined in the time section */
static void meta_note_write(int fd);   /* file-metadata scrub layer */
static void fd_meta_reset(int fd);
static uint64_t splitmix64_next(void); /* defined in the random section */

/* deterministic entropy fill, shared by the getrandom interposer and the
 * SIGSYS arm (needs only g_shm, so it stays valid during the destructor) */
static void fill_entropy(uint8_t *p, size_t left) {
    while (left) {
        uint64_t v = splitmix64_next();
        size_t n = left < 8 ? left : 8;
        memcpy(p, &v, n);
        p += n;
        left -= n;
    }
}

static long shim_raw_syscall6(long nr, long a1, long a2, long a3, long a4,
                              long a5, long a6) {
    register long r10 __asm__("r10") = a4;
    register long r8 __asm__("r8") = a5;
    register long r9 __asm__("r9") = a6;
    long ret;
    __asm__ volatile("syscall"
                     : "=a"(ret)
                     : "a"(nr), "D"(a1), "S"(a2), "d"(a3), "r"(r10), "r"(r8),
                       "r"(r9)
                     : "rcx", "r11", "memory");
    return ret;
}

/* -- vDSO patch -------------------------------------------------------- */

#include <elf.h>
#include <link.h>
#include <sys/auxv.h>

static int vdso_repl_clock_gettime(clockid_t clk, struct timespec *ts) {
    if (!g_shm)
        return (int)shim_raw_syscall6(SYS_clock_gettime, clk, (long)ts, 0, 0,
                                      0, 0);
    uint64_t now = sim_now_ns();
    if (ts) {
        ts->tv_sec = (time_t)(now / 1000000000ull);
        ts->tv_nsec = (long)(now % 1000000000ull);
    }
    return 0;
}

static int vdso_repl_gettimeofday(struct timeval *tv, void *tz) {
    if (!g_shm)
        return (int)shim_raw_syscall6(SYS_gettimeofday, (long)tv, (long)tz, 0,
                                      0, 0, 0);
    uint64_t now = sim_now_ns();
    if (tv) {
        tv->tv_sec = (time_t)(now / 1000000000ull);
        tv->tv_usec = (suseconds_t)((now % 1000000000ull) / 1000);
    }
    return 0;
}

static time_t vdso_repl_time(time_t *tloc) {
    if (!g_shm)
        return (time_t)shim_raw_syscall6(SYS_time, (long)tloc, 0, 0, 0, 0, 0);
    time_t t = (time_t)(sim_now_ns() / 1000000000ull);
    if (tloc) *tloc = t;
    return t;
}

static int vdso_repl_clock_getres(clockid_t clk, struct timespec *ts) {
    (void)clk;
    if (ts) {
        ts->tv_sec = 0;
        ts->tv_nsec = 1; /* the simulated clock is integer nanoseconds */
    }
    return 0;
}

static long vdso_repl_getcpu(unsigned *cpu, unsigned *node, void *unused) {
    (void)unused; /* deterministic: every plugin sees cpu 0 / node 0 */
    if (cpu) *cpu = 0;
    if (node) *node = 0;
    return 0;
}

/* minimal in-memory vDSO symbol lookup (the classic parse_vdso walk:
 * program headers -> PT_DYNAMIC -> DT_SYMTAB/DT_STRTAB/DT_HASH) */
static void *vdso_sym(unsigned long base, const char *name) {
    const Elf64_Ehdr *eh = (const Elf64_Ehdr *)base;
    const Elf64_Phdr *ph = (const Elf64_Phdr *)(base + eh->e_phoff);
    const Elf64_Dyn *dyn = NULL;
    unsigned long load_off = base;
    for (int i = 0; i < eh->e_phnum; i++) {
        if (ph[i].p_type == PT_DYNAMIC)
            dyn = (const Elf64_Dyn *)(base + ph[i].p_offset);
        else if (ph[i].p_type == PT_LOAD)
            load_off = base + ph[i].p_offset - ph[i].p_vaddr;
    }
    if (!dyn) return NULL;
    const Elf64_Sym *symtab = NULL;
    const char *strtab = NULL;
    const uint32_t *hash = NULL;
    for (const Elf64_Dyn *d = dyn; d->d_tag != DT_NULL; d++) {
        void *p = (void *)(load_off + d->d_un.d_ptr);
        if (d->d_tag == DT_SYMTAB) symtab = p;
        else if (d->d_tag == DT_STRTAB) strtab = p;
        else if (d->d_tag == DT_HASH) hash = p;
    }
    if (!symtab || !strtab || !hash) return NULL;
    uint32_t nchain = hash[1];
    for (uint32_t i = 0; i < nchain; i++) {
        if (symtab[i].st_name && strcmp(strtab + symtab[i].st_name, name) == 0
            && symtab[i].st_shndx != SHN_UNDEF)
            return (void *)(load_off + symtab[i].st_value);
    }
    return NULL;
}

static void vdso_hijack(unsigned long base, const char *name, void *target) {
    uint8_t *sym = vdso_sym(base, name);
    if (!sym) return;
    /* mov rax, imm64; jmp rax — 12 bytes, may straddle a page boundary */
    unsigned long page = (unsigned long)sym & ~0xFFFul;
    size_t span = ((unsigned long)sym + 12 > page + 0x1000) ? 0x2000 : 0x1000;
    if (mprotect((void *)page, span, PROT_READ | PROT_WRITE | PROT_EXEC) != 0)
        return;
    uint8_t code[12] = {0x48, 0xB8, 0, 0, 0, 0, 0, 0, 0, 0, 0xFF, 0xE0};
    memcpy(code + 2, &target, 8);
    memcpy(sym, code, sizeof(code));
    mprotect((void *)page, span, PROT_READ | PROT_EXEC);
}

static void patch_vdso(void) {
    unsigned long base = getauxval(AT_SYSINFO_EHDR);
    if (!base) return; /* no vDSO mapped: nothing to bypass us */
    vdso_hijack(base, "__vdso_clock_gettime", (void *)vdso_repl_clock_gettime);
    vdso_hijack(base, "__vdso_gettimeofday", (void *)vdso_repl_gettimeofday);
    vdso_hijack(base, "__vdso_time", (void *)vdso_repl_time);
    vdso_hijack(base, "__vdso_clock_getres", (void *)vdso_repl_clock_getres);
    vdso_hijack(base, "__vdso_getcpu", (void *)vdso_repl_getcpu);
}

/* -- seccomp SIGSYS backstop ------------------------------------------- */

#include <linux/audit.h>
#include <linux/filter.h>
#include <linux/seccomp.h>
#include <sys/prctl.h>
#include <ucontext.h>

static unsigned long g_text_lo, g_text_hi;
static int g_seccomp_on; /* filter actually installed in THIS process */

static int text_range_cb(struct dl_phdr_info *info, size_t sz, void *data) {
    (void)sz;
    (void)data;
    unsigned long probe = (unsigned long)(void *)&shim_raw_syscall6;
    for (int i = 0; i < info->dlpi_phnum; i++) {
        const Elf64_Phdr *p = &info->dlpi_phdr[i];
        if (p->p_type != PT_LOAD || !(p->p_flags & PF_X)) continue;
        unsigned long lo = info->dlpi_addr + p->p_vaddr;
        unsigned long hi = lo + p->p_memsz;
        if (probe >= lo && probe < hi) {
            g_text_lo = lo;
            g_text_hi = hi;
            return 1;
        }
    }
    return 0;
}

/* Dispatch of trapped syscalls to the wrapper logic lives at the end of
 * the file, after every wrapper it routes through. */
static long emu_owned_syscall(long nr, long a1, long a2, long a3, long a4,
                              long a5, long a6, int *handled);

/* -- syscall-user-dispatch (primary backstop) --------------------------- */

#ifndef PR_SET_SYSCALL_USER_DISPATCH
#define PR_SET_SYSCALL_USER_DISPATCH 59
#define PR_SYS_DISPATCH_OFF 0
#define PR_SYS_DISPATCH_ON 1
#define SYSCALL_DISPATCH_FILTER_ALLOW 0
#define SYSCALL_DISPATCH_FILTER_BLOCK 1
#endif

/* One selector byte for the whole process (each thread registers the same
 * address).  It stays BLOCK for the process's lifetime; the allowed text
 * region — not selector flipping — is what lets the shim's own syscalls
 * through, so there is no enable/disable race to manage.  The only
 * exception is the pthread_create bracket (see there). */
static volatile char g_sud_selector = SYSCALL_DISPATCH_FILTER_ALLOW;
static int g_sud_on;

/* SUD registration is per-thread and is NOT inherited by fork children or
 * new threads (verified empirically; unlike a seccomp filter it is also
 * reset by execve — the property that makes native exec workable).  Every
 * fork child and pthread re-arms itself from shim text before running
 * app code. */
static int sud_arm(void) {
    return (int)shim_raw_syscall6(SYS_prctl, PR_SET_SYSCALL_USER_DISPATCH,
                                  PR_SYS_DISPATCH_ON, (long)g_text_lo,
                                  (long)(g_text_hi - g_text_lo),
                                  (long)&g_sud_selector, 0);
}

static void sigsys_handler(int sig, siginfo_t *si, void *uctx) {
    (void)sig;
    (void)si;
    int saved_errno = errno; /* handlers must be errno-transparent */
    ucontext_t *uc = uctx;
    greg_t *gr = uc->uc_mcontext.gregs;
    long nr = gr[REG_RAX];
    if (nr == SYS_rt_sigreturn) {
        /* An app signal handler is returning: its libc restorer's
         * rt_sigreturn was dispatched here, so the kernel would read the
         * signal frame at OUR stack depth, not the original one.  Emulate
         * in user space instead: at the original syscall insn, RSP points
         * at the interrupted frame's ucontext (the restorer's return
         * address has been consumed) — adopt that saved context, sigmask
         * and fpstate pointer included, as this handler's own; our
         * sigreturn then restores the state the app's frame described. */
        ucontext_t *orig = (ucontext_t *)gr[REG_RSP];
        *uc = *orig;
        errno = saved_errno;
        return;
    }
    long a1 = gr[REG_RDI], a2 = gr[REG_RSI], a3 = gr[REG_RDX];
    long a4 = gr[REG_R10], a5 = gr[REG_R8], a6 = gr[REG_R9];
    unsigned long insn_ip = (unsigned long)gr[REG_RIP] - 2; /* rip is past
                                                the 2-byte syscall insn */
    if (nr == SYS_rt_sigprocmask &&
        !(insn_ip >= g_text_lo && insn_ip < g_text_hi)) {
        /* An app mask change must land in uc_sigmask — the kernel
         * restores THAT at our sigreturn, so a mask set natively inside
         * this handler would be silently undone.  Operate on the saved
         * context directly (SIGSYS stripped: blocking it turns the next
         * dispatch into a forced kill) and mirror the app's logical
         * blocked set for the manager's park-release decisions.
         * sigsetsize != 8 gets the kernel's own answer (-EINVAL) rather
         * than a native fallthrough whose effect sigreturn would undo. */
        uint64_t *ucm = (uint64_t *)&uc->uc_sigmask;
        uint64_t old = *ucm;
        long r = 0;
        if ((size_t)a4 != 8) {
            gr[REG_RAX] = -EINVAL;
            errno = saved_errno;
            return;
        }
        if (a2) {
            uint64_t m;
            memcpy(&m, (void *)a2, 8);
            uint64_t nw = old;
            if ((int)a1 == SIG_BLOCK) nw = old | m;
            else if ((int)a1 == SIG_UNBLOCK) nw = old & ~m;
            else if ((int)a1 == SIG_SETMASK) nw = m;
            else r = -EINVAL;
            if (r == 0) {
                nw &= ~(1ull << (SIGSYS - 1));
                *ucm = nw;
                /* per-THREAD mirror (cur_shm): sigmasks are thread state —
                 * the manager checks the parked entity's own channel */
                shim_shmem *mshm = cur_shm();
                if (mshm)
                    __atomic_store_n(&mshm->blocked_signals, nw,
                                     __ATOMIC_RELAXED);
            }
        }
        if (r == 0 && a3) memcpy((void *)a3, &old, 8);
        gr[REG_RAX] = r;
        errno = saved_errno;
        return;
    }
    long ret;
    int handled = 0;
    /* Guard on g_shm, not g_ready: during the destructor (g_ready==0, shm
     * still mapped) emulation keeps working.  A trap whose instruction
     * pointer lies inside OUR OWN text is a raw helper call caught by a
     * stale seccomp generation (a pre-exec filter whose allow range points
     * at the previous image): straight to the kernel, never re-dispatched. */
    if (!g_shm || (insn_ip >= g_text_lo && insn_ip < g_text_hi)) {
        ret = shim_raw_syscall6(nr, a1, a2, a3, a4, a5, a6);
    } else {
        /* raw-clone adoption needs the full context; save/restore so a
         * NESTED dispatch (SA_NODEFER) can't wipe the outer frame's */
        void *prev_uc = t_cur_uc;
        t_cur_uc = uc;
        ret = emu_owned_syscall(nr, a1, a2, a3, a4, a5, a6, &handled);
        t_cur_uc = prev_uc;
        if (!handled) ret = shim_raw_syscall6(nr, a1, a2, a3, a4, a5, a6);
    }
    gr[REG_RAX] = ret;
    errno = saved_errno;
}

/* sigreturn must itself come from the allowed region: with the dispatch
 * selector at BLOCK and SIGSYS masked inside the handler, a libc restorer
 * would trap and the forced SIGSYS would kill the process. */
__attribute__((naked, used)) static void shim_restore_rt(void) {
    __asm__ volatile("mov $15, %%rax\n\t" /* SYS_rt_sigreturn */
                     "syscall" ::: "memory");
}

/* kernel-facing sigaction (glibc's struct differs; the handler must be
 * installed with OUR restorer, which libc sigaction does not allow) */
struct shim_ksigaction {
    void *handler;
    unsigned long flags;
    void (*restorer)(void);
    uint64_t mask;
};

#define SHIM_SA_SIGINFO 4UL
#define SHIM_SA_RESTORER 0x04000000UL
#define SHIM_SA_ONSTACK 0x08000000UL
#define SHIM_SA_RESTART 0x10000000UL
#define SHIM_SA_NODEFER 0x40000000UL

static int install_sigsys_handler(void) {
    struct shim_ksigaction ksa;
    memset(&ksa, 0, sizeof(ksa));
    ksa.handler = (void *)sigsys_handler;
    /* SA_NODEFER: the dispatcher's wrappers may reach libc internals
     * (allocators, stdio) whose syscalls trap again — nested handling must
     * work, as in the reference (shim_seccomp.c SA_NODEFER comment) */
    ksa.flags = SHIM_SA_SIGINFO | SHIM_SA_RESTORER | SHIM_SA_RESTART |
                SHIM_SA_NODEFER;
    ksa.restorer = shim_restore_rt;
    return (int)shim_raw_syscall6(SYS_rt_sigaction, SIGSYS, (long)&ksa, 0, 8,
                                  0, 0);
}

/* -- legacy seccomp filter (fallback for kernels without SUD) ----------- */

static void install_seccomp(void) {
    if ((g_text_lo >> 32) != ((g_text_hi - 1) >> 32) ||
        (uint32_t)g_text_hi == 0) {
        shim_warn("seccomp backstop disabled: shim text range not usable");
        return;
    }
    uint32_t ip_off = 8; /* offsetof(struct seccomp_data, instruction_pointer) */
    uint32_t ip_hi = (uint32_t)(g_text_lo >> 32);
    uint32_t lo_start = (uint32_t)g_text_lo;
    uint32_t lo_end = (uint32_t)g_text_hi;
#ifndef SECCOMP_RET_KILL_PROCESS
#define SECCOMP_RET_KILL_PROCESS 0x80000000U
#endif
    /* non-x86_64 arch (int 0x80 compat) and x32-ABI syscalls would use a
     * different nr numbering and silently bypass the trap set: kill, as
     * the reference's filter does for mismatched arch */
    struct sock_filter filt[] = {
        /* 0 */ BPF_STMT(BPF_LD | BPF_W | BPF_ABS, 4 /* arch */),
        /* 1 */ BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, AUDIT_ARCH_X86_64, 1, 0),
        /* 2 */ BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_KILL_PROCESS),
        /* 3 */ BPF_STMT(BPF_LD | BPF_W | BPF_ABS, ip_off + 4),
        /* 4 */ BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, ip_hi, 0, 4),
        /* 5 */ BPF_STMT(BPF_LD | BPF_W | BPF_ABS, ip_off),
        /* 6 */ BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, lo_start, 0, 2),
        /* 7 */ BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, lo_end, 1, 0),
        /* 8 */ BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_ALLOW),
        /* 9 */ BPF_STMT(BPF_LD | BPF_W | BPF_ABS, 0 /* nr */),
        /* 10 */ BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, 0x40000000 /* x32 */, 8, 0),
        /* 11 */ BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, SYS_clock_gettime, 6, 0),
        /* 12 */ BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, SYS_gettimeofday, 5, 0),
        /* 13 */ BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, SYS_time, 4, 0),
        /* 14 */ BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, SYS_nanosleep, 3, 0),
        /* 15 */ BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, SYS_clock_nanosleep, 2, 0),
        /* 16 */ BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, SYS_getrandom, 1, 0),
        /* 17 */ BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_ALLOW),
        /* 18 */ BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_TRAP),
        /* 19 */ BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_KILL_PROCESS),
    };
    struct sock_fprog prog = {sizeof(filt) / sizeof(filt[0]), filt};
    if (prctl(PR_SET_NO_NEW_PRIVS, 1, 0, 0, 0) != 0 ||
        prctl(PR_SET_SECCOMP, SECCOMP_MODE_FILTER, &prog) != 0) {
        shim_warn("seccomp backstop disabled: filter install failed");
        return;
    }
    g_seccomp_on = 1;
}

/* -- backstop selection ------------------------------------------------- */

static void install_backstop(void) {
    if (!dl_iterate_phdr(text_range_cb, NULL)) {
        shim_warn("raw-syscall backstop disabled: shim text not found");
        return;
    }
    if (install_sigsys_handler() != 0) {
        shim_warn("raw-syscall backstop disabled: cannot install SIGSYS "
                  "handler");
        return;
    }
    const char *no_sud = getenv("SHADOW_TPU_SUD");
    if ((!no_sud || strcmp(no_sud, "0") != 0) && sud_arm() == 0) {
        g_sud_on = 1;
        g_sud_selector = SYSCALL_DISPATCH_FILTER_BLOCK;
        return;
    }
    /* kernel without syscall-user-dispatch (< 5.11) or SHADOW_TPU_SUD=0:
     * narrow seccomp trap of the time/sleep/entropy set only */
    install_seccomp();
}

static int tsc_chain_sigaction(const struct sigaction *act,
                               struct sigaction *oldact);
static void tsc_disarm_for_exec(void);
static int g_tsc_on; /* defined logically with the TSC emulation below */

/* Mirror an installed disposition into the manager-visible bitmaps: the
 * handled bit gates EINTR completion of parked calls; the ignored bit
 * keeps an explicit SIG_IGN from reading as SIG_DFL (whose default-fatal
 * action releases parks).  Process-wide state lives on the MAIN channel
 * regardless of the calling thread, matching POSIX disposition scope. */
static void publish_disposition(int signum, sighandler_t handler) {
    if (!g_shm || signum < 1 || signum > 64) return;
    uint64_t bit = 1ull << (signum - 1);
    if (handler != SIG_DFL && handler != SIG_IGN)
        __atomic_or_fetch(&g_shm->handled_signals, bit, __ATOMIC_RELAXED);
    else
        __atomic_and_fetch(&g_shm->handled_signals, ~bit, __ATOMIC_RELAXED);
    if (handler == SIG_IGN)
        __atomic_or_fetch(&g_shm->ignored_signals, bit, __ATOMIC_RELAXED);
    else
        __atomic_and_fetch(&g_shm->ignored_signals, ~bit, __ATOMIC_RELAXED);
}

/* The app must not displace the SIGSYS backstop — but only when the
 * backstop is actually installed here; otherwise apps that sandbox
 * themselves (own seccomp + SIGSYS handler) must keep working. */
int sigaction(int signum, const struct sigaction *act,
              struct sigaction *oldact) {
    static int (*real_sa)(int, const struct sigaction *, struct sigaction *);
    if (!real_sa) *(void **)&real_sa = dlsym(RTLD_NEXT, "sigaction");
    if ((g_seccomp_on || g_sud_on) && signum == SIGSYS && act != NULL) {
        if (oldact) memset(oldact, 0, sizeof(*oldact));
        return 0; /* accepted and ignored: the backstop stays */
    }
    if (signum == SIGSEGV && tsc_chain_sigaction(act, oldact)) {
        /* absorbed: the TSC trap stays, app handler chained — but the
         * disposition is real and must reach the manager's bitmaps */
        if (act) publish_disposition(signum, act->sa_handler);
        return 0;
    }
    int r = real_sa(signum, act, oldact);
    if (r == 0 && act) publish_disposition(signum, act->sa_handler);
    return r;
}

/* glibc's signal() resolves through internal __sigaction, bypassing the
 * sigaction interposer — cover it directly */
sighandler_t signal(int signum, sighandler_t handler) {
    static sighandler_t (*real_signal)(int, sighandler_t);
    if (!real_signal) *(void **)&real_signal = dlsym(RTLD_NEXT, "signal");
    if ((g_seccomp_on || g_sud_on) && signum == SIGSYS) return SIG_DFL;
    if (signum == SIGSEGV && g_tsc_on) {
        struct sigaction sa_c;
        memset(&sa_c, 0, sizeof(sa_c));
        sa_c.sa_handler = handler;
        struct sigaction old;
        tsc_chain_sigaction(&sa_c, &old);
        publish_disposition(signum, handler);
        return (old.sa_flags & SA_SIGINFO) ? SIG_DFL : old.sa_handler;
    }
    sighandler_t r = real_signal(signum, handler);
    if (r != SIG_ERR) publish_disposition(signum, handler);
    return r;
}

/* -- RDTSC/RDTSCP emulation (the reference's shim_insn_emu.c) ----------- */
/* TSC-reading code (glibc internals, language runtimes, OpenSSL timing
 * paths) would observe REAL time and silently break determinism.
 * PR_SET_TSC(PR_TSC_SIGSEGV) makes every rdtsc/rdtscp fault; the handler
 * decodes the instruction and serves monotone simulated cycles (a 1 GHz
 * virtual TSC: one cycle per simulated nanosecond).  Faults that are not
 * TSC reads restore the default disposition and re-execute, so real
 * crashes still crash.  An app installing its own SIGSEGV handler is
 * CHAINED: the shim keeps its handler (PR_SET_TSC is per-thread state,
 * so dropping it on one thread would leave others faulting into the
 * app's handler) and forwards non-TSC faults to the app's. */
#ifndef PR_SET_TSC
#define PR_SET_TSC 26
#define PR_TSC_ENABLE 1
#define PR_TSC_SIGSEGV 2
#endif
/* the app's own SIGSEGV disposition, chained behind the TSC trap */
static struct sigaction g_app_segv;
static int g_app_segv_set;

static void tsc_segv_handler(int sig, siginfo_t *si, void *uctx) {
    ucontext_t *uc = uctx;
    greg_t *gr = uc->uc_mcontext.gregs;
    const uint8_t *ip = (const uint8_t *)gr[REG_RIP];
    if (g_shm && ip && ip[0] == 0x0F &&
        (ip[1] == 0x31 || (ip[1] == 0x01 && ip[2] == 0xF9))) {
        uint64_t cycles = sim_now_ns();
        gr[REG_RAX] = (greg_t)(cycles & 0xFFFFFFFFull);
        gr[REG_RDX] = (greg_t)(cycles >> 32);
        if (ip[1] == 0x01) {
            gr[REG_RCX] = 0; /* rdtscp: IA32_TSC_AUX = cpu 0 */
            gr[REG_RIP] += 3;
        } else {
            gr[REG_RIP] += 2;
        }
        return;
    }
    /* a real fault: forward to the app's handler if it installed one */
    if (g_app_segv_set) {
        if (g_app_segv.sa_flags & SA_SIGINFO) {
            if (g_app_segv.sa_sigaction != NULL) {
                g_app_segv.sa_sigaction(sig, si, uctx);
                return;
            }
        } else if (g_app_segv.sa_handler != SIG_DFL &&
                   g_app_segv.sa_handler != SIG_IGN) {
            g_app_segv.sa_handler(sig);
            return;
        } else if (g_app_segv.sa_handler == SIG_IGN) {
            return;
        }
    }
    /* no app handler: restore the default disposition and return — the
     * faulting instruction re-executes and crashes properly */
    struct shim_ksigaction dfl;
    memset(&dfl, 0, sizeof(dfl));
    shim_raw_syscall6(SYS_rt_sigaction, SIGSEGV, (long)&dfl, 0, 8, 0, 0);
}

static void tsc_disarm_for_exec(void) {
    if (!g_tsc_on) return;
    shim_raw_syscall6(SYS_prctl, PR_SET_TSC, PR_TSC_ENABLE, 0, 0, 0, 0);
}

static void tsc_arm(void) {
    struct shim_ksigaction ksa;
    memset(&ksa, 0, sizeof(ksa));
    ksa.handler = (void *)tsc_segv_handler;
    ksa.flags = SHIM_SA_SIGINFO | SHIM_SA_RESTORER;
    ksa.restorer = shim_restore_rt;
    if (shim_raw_syscall6(SYS_rt_sigaction, SIGSEGV, (long)&ksa, 0, 8, 0,
                          0) != 0)
        return;
    if (shim_raw_syscall6(SYS_prctl, PR_SET_TSC, PR_TSC_SIGSEGV, 0, 0, 0,
                          0) == 0)
        g_tsc_on = 1;
}

/* App SIGSEGV registrations chain behind the trap instead of displacing
 * it (PR_SET_TSC is per-thread: disabling it here would only cover the
 * calling thread and leave other threads faulting into the app handler
 * with no emulation).  Returns 1 when the registration was absorbed. */
static int tsc_chain_sigaction(const struct sigaction *act,
                               struct sigaction *oldact) {
    if (!g_tsc_on) return 0;
    if (oldact) {
        if (g_app_segv_set) *oldact = g_app_segv;
        else memset(oldact, 0, sizeof(*oldact));
    }
    if (act) {
        g_app_segv = *act;
        g_app_segv_set = 1;
    }
    return 1;
}

/* -- busy-loop preemption (the reference's preempt.rs) ------------------ */
/* A plugin spinning on locally-serviced calls (clock_gettime reads the
 * shmem clock — no manager hop) would never yield its turn and livelock
 * the round.  When the CPU model is on, a CPU-time interval timer fires
 * SIGVTALRM after each quantum of native CPU time and forces a yield that
 * charges the quantum as simulated time.  shim_call masks SIGVTALRM
 * during exchanges, so the forced yield only ever lands between calls —
 * the same deferral discipline as app signal handlers.  Inherently
 * wall-clock-dependent, so it is config-gated
 * (general.model_unblocked_syscall_latency), exactly like the reference's
 * feature. */
static long g_preempt_ns;

static void preempt_handler(int sig) {
    (void)sig;
    if (!g_ready || t_exit_sent) return;
    int saved_errno = errno;
    int64_t args[6] = {g_preempt_ns, 0, 0, 0, 0, 0};
    shim_call(SHIM_OP_PREEMPT, args, NULL, 0, NULL, NULL, NULL);
    errno = saved_errno;
}

static void preempt_arm(void) {
    if (!g_preempt_ns) return;
    struct shim_ksigaction ksa;
    memset(&ksa, 0, sizeof(ksa));
    ksa.handler = (void *)preempt_handler;
    ksa.flags = SHIM_SA_RESTORER | SHIM_SA_RESTART;
    ksa.restorer = shim_restore_rt;
    shim_raw_syscall6(SYS_rt_sigaction, SIGVTALRM, (long)&ksa, 0, 8, 0, 0);
    struct itimerval itv;
    itv.it_interval.tv_sec = g_preempt_ns / 1000000000L;
    itv.it_interval.tv_usec = (g_preempt_ns % 1000000000L) / 1000;
    itv.it_value = itv.it_interval;
    shim_raw_syscall6(SYS_setitimer, 1 /* ITIMER_VIRTUAL */, (long)&itv, 0,
                      0, 0, 0);
}

static int (*g_real_pthread_create)(pthread_t *, const pthread_attr_t *,
                                    void *(*)(void *), void *);

__attribute__((constructor)) static void shim_init(void) {
    const char *path = getenv("SHADOW_TPU_SHM");
    resolve_reals();
    /* raw-clone adoption runs from the SIGSYS handler, where dlsym could
     * allocate: resolve pthread_create now */
    *(void **)&g_real_pthread_create = dlsym(RTLD_NEXT, "pthread_create");
    if (!path) return; /* not under the simulator: become a no-op */
    shim_attach(path);
    g_ready = 1;
    const char *pq = getenv("SHADOW_TPU_PREEMPT_NS");
    if (pq) g_preempt_ns = atol(pq);
    /* backstops before the first handshake (the reference's init order:
     * shmem -> seccomp -> vdso, shim.c:108-122); default on, disabled via
     * experimental.use_vdso_patching / use_seccomp */
    const char *vd = getenv("SHADOW_TPU_VDSO");
    if (!vd || strcmp(vd, "0") != 0) patch_vdso();
    const char *sc = getenv("SHADOW_TPU_SECCOMP");
    if (!sc || strcmp(sc, "0") != 0) install_backstop();
    const char *tsc = getenv("SHADOW_TPU_TSC");
    if (!tsc || strcmp(tsc, "0") != 0) tsc_arm();
    preempt_arm();
    /* report in and wait for the go signal: from here on the plugin only
     * runs while the manager has handed it the turn */
    shim_call(SHIM_OP_START, NULL, NULL, 0, NULL, NULL, NULL);
}

/* exit() may run on a secondary thread: the manager is waiting on THAT
 * thread's channel, so the farewell must ride it.  Also invoked by the
 * raw-syscall dispatcher when an app calls exit_group directly (which
 * skips destructors). */
static void send_farewell(void) {
    if (!g_ready) return;
    g_ready = 0;
    shim_msg *tx = &cur_shm()->to_shadow;
    tx->op = SHIM_OP_EXIT;
    tx->args[0] = g_exit_code;
    for (int i = 1; i < 6; i++) tx->args[i] = 0;
    tx->payload_len = 0;
    msg_publish(tx); /* no reply: the process is on its way out */
}

__attribute__((destructor)) static void shim_fini(void) { send_farewell(); }

/* ----------------------------------------------------- virtual fd table */

static int is_vfd(int fd) {
    /* also the lazy-init hook: wrappers can be reached from other libraries'
     * constructors before our own constructor resolved the real symbols */
    if (!real_socket) resolve_reals();
    return g_ready && fd >= 0 && fd < SHIM_MAX_FDS && vfd_kind[fd] == VK_SOCKET;
}

/* Reserve a real kernel fd slot for a simulated socket so the number can't
 * collide with the plugin's own fds. */
/* one-time operator-visible warning when a compile-time table cap is
 * hit — the errno alone (EMFILE/ENOSPC) is correct but easy to miss in
 * an app that retries quietly */
static void cap_warn(int id, const char *what, int cap) {
    static unsigned warned; /* one bit per distinct cap */
    if (!(warned & (1u << id))) {
        warned |= 1u << id;
        /* raw write: reachable from the SIGSYS capture path, where
         * stdio/malloc locks may be held by the interrupted code */
        char buf[160];
        int n = snprintf(buf, sizeof(buf),
                         "shadow-shim: %s capacity (%d) exhausted - raise "
                         "the compile-time cap in shadow_shim.c\n", what,
                         cap);
        if (n > 0)
            shim_raw_syscall6(SYS_write, 2, (long)buf,
                              n < (int)sizeof(buf) ? n : (int)sizeof(buf),
                              0, 0, 0);
    }
}

static int reserve_fd(void) {
    /* O_PATH: every uninterposed data syscall on the reservation (readv,
     * recvmsg, a dup...) fails loudly with EBADF instead of reading
     * /dev/null's silent EOF */
    int fd = open("/dev/null", O_PATH | O_CLOEXEC);
    if (fd < 0) return -1;
    if (fd >= SHIM_MAX_FDS) {
        real_close(fd);
        cap_warn(0, "fd table (SHIM_MAX_FDS)", SHIM_MAX_FDS);
        errno = EMFILE;
        return -1;
    }
    return fd;
}

static void vfd_register(int fd, int nonblock, int stream) {
    vfd_kind[fd] = VK_SOCKET;
    vfd_nonblock[fd] = (uint8_t)(nonblock != 0);
    vfd_stream[fd] = (uint8_t)(stream != 0);
    vfd_listening[fd] = 0;
}

static void vfd_release(int fd) {
    vfd_kind[fd] = VK_NONE;
    vfd_nonblock[fd] = 0;
    vfd_stream[fd] = 0;
    vfd_listening[fd] = 0;
    real_close(fd); /* free the /dev/null reservation */
}

/* ---------------------------------------------- AF_NETLINK emulation */
/* NETLINK_ROUTE answered ENTIRELY in the shim from the simulated
 * interface config (lo + eth0 with the host's simulated IP) — a real
 * netlink socket would leak the host machine's interfaces into the
 * simulation.  Covers the dump surface real software uses to enumerate
 * interfaces (glibc getifaddrs internals, iproute2, the Go net package:
 * RTM_GETLINK / RTM_GETADDR with NLM_F_DUMP); modification requests are
 * refused with EPERM (the simulated net is static).  The reference
 * implements the same subset manager-side (socket/netlink.rs); here the
 * answers are deterministic canned state, so no manager round-trip is
 * needed. */
#include <linux/netlink.h>
#include <linux/rtnetlink.h>
#include <net/if.h>
#include <net/if_arp.h>

static int hosts_lookup(const char *name, uint32_t *ip_out);

typedef struct {
    uint32_t pid;     /* bound netlink pid */
    uint16_t pending; /* RTM_GETLINK / RTM_GETADDR / 0 */
    uint32_t seq;
    uint8_t phase;    /* 0 = payload batch next, 1 = NLMSG_DONE next */
    uint8_t ack;      /* 1 = NLMSG_ERROR queued */
    int ack_err;
    uint32_t ack_seq;
} shim_nl_state;
static shim_nl_state nl_state[SHIM_MAX_FDS];

static int is_nlfd(int fd) {
    return g_ready && fd >= 0 && fd < SHIM_MAX_FDS &&
           vfd_kind[fd] == VK_NETLINK;
}

static long raw_gettid(void) { return shim_raw_syscall6(SYS_gettid, 0, 0, 0, 0, 0, 0); }

static size_t nl_attr_put(char *p, size_t off, unsigned short type,
                          const void *data, size_t len) {
    struct rtattr *rta = (struct rtattr *)(p + off);
    rta->rta_type = type;
    rta->rta_len = (unsigned short)RTA_LENGTH(len);
    memcpy(RTA_DATA(rta), data, len);
    return off + RTA_ALIGN(rta->rta_len);
}

static size_t nl_link_msg(char *p, size_t off, const shim_nl_state *st,
                          int idx, const char *name, unsigned flags,
                          unsigned short arphrd, unsigned mtu,
                          const unsigned char mac[6]) {
    size_t start = off;
    struct nlmsghdr *nh = (struct nlmsghdr *)(p + off);
    off += NLMSG_HDRLEN;
    struct ifinfomsg ifi;
    memset(&ifi, 0, sizeof(ifi));
    ifi.ifi_family = AF_UNSPEC;
    ifi.ifi_type = arphrd;
    ifi.ifi_index = idx;
    ifi.ifi_flags = flags;
    ifi.ifi_change = 0xFFFFFFFFu;
    memcpy(p + off, &ifi, sizeof(ifi));
    off += NLMSG_ALIGN(sizeof(ifi));
    off = nl_attr_put(p, off, IFLA_IFNAME, name, strlen(name) + 1);
    off = nl_attr_put(p, off, IFLA_MTU, &mtu, 4);
    off = nl_attr_put(p, off, IFLA_ADDRESS, mac, 6);
    unsigned char up = 6; /* IF_OPER_UP */
    off = nl_attr_put(p, off, IFLA_OPERSTATE, &up, 1);
    unsigned txq = 1000; /* present so iproute2 skips its ioctl fallback */
    off = nl_attr_put(p, off, IFLA_TXQLEN, &txq, 4);
    nh->nlmsg_len = (uint32_t)(off - start);
    nh->nlmsg_type = RTM_NEWLINK;
    nh->nlmsg_flags = NLM_F_MULTI;
    nh->nlmsg_seq = st->seq;
    nh->nlmsg_pid = st->pid;
    return off;
}

static size_t nl_addr_msg(char *p, size_t off, const shim_nl_state *st,
                          int idx, const char *label, uint32_t ip_be,
                          unsigned char prefix, unsigned char scope) {
    size_t start = off;
    struct nlmsghdr *nh = (struct nlmsghdr *)(p + off);
    off += NLMSG_HDRLEN;
    struct ifaddrmsg ifa;
    memset(&ifa, 0, sizeof(ifa));
    ifa.ifa_family = AF_INET;
    ifa.ifa_prefixlen = prefix;
    ifa.ifa_flags = IFA_F_PERMANENT;
    ifa.ifa_scope = scope;
    ifa.ifa_index = (unsigned)idx;
    memcpy(p + off, &ifa, sizeof(ifa));
    off += NLMSG_ALIGN(sizeof(ifa));
    off = nl_attr_put(p, off, IFA_ADDRESS, &ip_be, 4);
    off = nl_attr_put(p, off, IFA_LOCAL, &ip_be, 4);
    off = nl_attr_put(p, off, IFA_LABEL, label, strlen(label) + 1);
    nh->nlmsg_len = (uint32_t)(off - start);
    nh->nlmsg_type = RTM_NEWADDR;
    nh->nlmsg_flags = NLM_F_MULTI;
    nh->nlmsg_seq = st->seq;
    nh->nlmsg_pid = st->pid;
    return off;
}

static ssize_t nl_send(int fd, const void *buf, size_t n) {
    shim_nl_state *st = &nl_state[fd];
    size_t remaining = n;
    const struct nlmsghdr *nh = (const struct nlmsghdr *)buf;
    while (remaining >= sizeof(struct nlmsghdr) &&
           nh->nlmsg_len >= sizeof(struct nlmsghdr) &&
           nh->nlmsg_len <= remaining) {
        if (nh->nlmsg_type == RTM_GETLINK || nh->nlmsg_type == RTM_GETADDR) {
            st->pending = nh->nlmsg_type;
            st->seq = nh->nlmsg_seq;
            st->phase = 0;
        } else if (nh->nlmsg_type >= RTM_BASE) {
            /* modification request: the simulated net is static */
            st->ack = 1;
            st->ack_err = -EPERM;
            st->ack_seq = nh->nlmsg_seq;
        }
        size_t adv = NLMSG_ALIGN(nh->nlmsg_len);
        if (adv >= remaining) break;
        remaining -= adv;
        nh = (const struct nlmsghdr *)((const char *)nh + adv);
    }
    return (ssize_t)n;
}

static ssize_t nl_recv(int fd, void *buf, size_t n, int flags,
                       struct sockaddr *addr, socklen_t *alen) {
    shim_nl_state *st = &nl_state[fd];
    char pkt[1024];
    size_t len = 0;
    if (st->ack) {
        struct nlmsghdr *nh = (struct nlmsghdr *)pkt;
        struct nlmsgerr err;
        memset(&err, 0, sizeof(err));
        err.error = st->ack_err;
        err.msg.nlmsg_seq = st->ack_seq;
        nh->nlmsg_len = NLMSG_LENGTH(sizeof(err));
        nh->nlmsg_type = NLMSG_ERROR;
        nh->nlmsg_flags = 0;
        nh->nlmsg_seq = st->ack_seq;
        nh->nlmsg_pid = st->pid;
        memcpy(NLMSG_DATA(nh), &err, sizeof(err));
        len = nh->nlmsg_len;
        if (!(flags & MSG_PEEK)) st->ack = 0;
    } else if (st->pending && st->phase == 0) {
        uint32_t ip = 0;
        const char *hn = getenv("SHADOW_TPU_HOSTNAME");
        int have_ip = hn && hosts_lookup(hn, &ip) == 0;
        if (st->pending == RTM_GETLINK) {
            static const unsigned char mac0[6] = {0};
            unsigned char mac[6] = {0x02, 0x54, 0, 0, 0, 0};
            memcpy(mac + 2, &ip, 4); /* deterministic MAC from the sim IP */
            len = nl_link_msg(pkt, len, st, 1, "lo",
                              IFF_UP | IFF_LOOPBACK | IFF_RUNNING,
                              ARPHRD_LOOPBACK, 65536, mac0);
            if (have_ip)
                len = nl_link_msg(pkt, len, st, 2, "eth0",
                                  IFF_UP | IFF_BROADCAST | IFF_RUNNING |
                                      IFF_MULTICAST,
                                  ARPHRD_ETHER, 1500, mac);
        } else {
            len = nl_addr_msg(pkt, len, st, 1, "lo",
                              htonl(INADDR_LOOPBACK), 8, RT_SCOPE_HOST);
            if (have_ip)
                len = nl_addr_msg(pkt, len, st, 2, "eth0", ip, 8,
                                  RT_SCOPE_UNIVERSE);
        }
        if (!(flags & MSG_PEEK)) st->phase = 1;
    } else if (st->pending && st->phase == 1) {
        struct nlmsghdr *nh = (struct nlmsghdr *)pkt;
        nh->nlmsg_len = NLMSG_LENGTH(4);
        nh->nlmsg_type = NLMSG_DONE;
        nh->nlmsg_flags = NLM_F_MULTI;
        nh->nlmsg_seq = st->seq;
        nh->nlmsg_pid = st->pid;
        memset(NLMSG_DATA(nh), 0, 4);
        len = nh->nlmsg_len;
        if (!(flags & MSG_PEEK)) st->pending = 0;
    } else {
        errno = EAGAIN; /* nothing queued: only reachable without a dump
                           request in flight */
        return -1;
    }
    if (addr && alen && *alen >= sizeof(struct sockaddr_nl)) {
        struct sockaddr_nl *snl = (struct sockaddr_nl *)addr;
        memset(snl, 0, sizeof(*snl));
        snl->nl_family = AF_NETLINK;
        *alen = sizeof(*snl);
    }
    size_t copy = len < n ? len : n;
    memcpy(buf, pkt, copy);
    if (len > n && (flags & MSG_TRUNC)) return (ssize_t)len;
    return (ssize_t)copy;
}

/* --------------------------------------------------------------- time */

static uint64_t sim_now_ns(void) {
    /* each thread's channel clock is advanced on every reply to that
     * thread, so the thread's own channel holds its freshest time */
    return __atomic_load_n(&cur_shm()->sim_clock_ns, __ATOMIC_ACQUIRE);
}

/* the libc-level symbols delegate to the single vDSO-repl implementations
 * (one copy of the clock semantics for PLT, vDSO, and SIGSYS paths),
 * converting kernel-style negative returns to errno */
int clock_gettime(clockid_t clk, struct timespec *ts) {
    long r = vdso_repl_clock_gettime(clk, ts);
    if (r < 0) {
        errno = (int)-r;
        return -1;
    }
    return 0;
}

int gettimeofday(struct timeval *tv, void *tz) {
    long r = vdso_repl_gettimeofday(tv, tz);
    if (r < 0) {
        errno = (int)-r;
        return -1;
    }
    return 0;
}

time_t time(time_t *tloc) { return vdso_repl_time(tloc); }

/* -------------------------------------------------------------- sleep */

int nanosleep(const struct timespec *req, struct timespec *rem) {
    if (!g_ready) return syscall(SYS_nanosleep, req, rem);
    if (!req || req->tv_sec < 0 || req->tv_nsec < 0 ||
        req->tv_nsec >= 1000000000L) {
        errno = EINVAL;
        return -1;
    }
    int64_t args[6] = {0};
    args[0] = (int64_t)req->tv_sec * 1000000000ll + req->tv_nsec;
    int64_t reply[6];
    int64_t ret =
        shim_call(SHIM_OP_NANOSLEEP, args, NULL, 0, NULL, NULL, reply);
    if (ret == -EINTR) {
        /* a delivered signal interrupted the sleep; the manager reports
         * the remaining SIMULATED time (POSIX rem semantics) */
        if (rem) {
            rem->tv_sec = reply[1] / 1000000000ll;
            rem->tv_nsec = reply[1] % 1000000000ll;
        }
        errno = EINTR;
        return -1;
    }
    if (rem) rem->tv_sec = rem->tv_nsec = 0;
    return 0;
}

int usleep(useconds_t usec) {
    struct timespec ts = {usec / 1000000, (long)(usec % 1000000) * 1000};
    if (!g_ready) return syscall(SYS_nanosleep, &ts, NULL);
    return nanosleep(&ts, NULL);
}

unsigned int sleep(unsigned int seconds) {
    struct timespec ts = {seconds, 0};
    if (nanosleep(&ts, NULL) != 0) return seconds;
    return 0;
}

/* ------------------------------------------------------------- random */

static uint64_t splitmix64_next(void) {
    uint64_t c = __atomic_fetch_add(&g_shm->rng_counter, 1, __ATOMIC_RELAXED);
    uint64_t x = g_shm->rng_seed + c * 0x9E3779B97F4A7C15ull;
    x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ull;
    x = (x ^ (x >> 27)) * 0x94D049BB133111EBull;
    return x ^ (x >> 31);
}

ssize_t getrandom(void *buf, size_t buflen, unsigned int flags) {
    if (!g_shm) {
        long r = shim_raw_syscall6(SYS_getrandom, (long)buf, (long)buflen,
                                   flags, 0, 0, 0);
        if (r < 0) {
            errno = (int)-r;
            return -1;
        }
        return (ssize_t)r;
    }
    fill_entropy(buf, buflen);
    return (ssize_t)buflen;
}

/* OpenSSL-level RNG override (the reference's preload-openssl/rng.c):
 * TLS libraries seed from RDRAND and other in-process sources that the
 * syscall interposition never sees, so HTTPS-speaking apps would leak
 * nondeterminism through session keys, nonces, and hello randoms.
 * Interposing the RAND_* API itself closes that hole for any app that
 * links OpenSSL dynamically; apps without OpenSSL never bind these
 * symbols.  Outside the simulation each call forwards to the real
 * library (or to getrandom when none is loaded). */

static void *rand_real(const char *name, void **cache) {
    if (!*cache) *cache = dlsym(RTLD_NEXT, name);
    return *cache;
}

/* raw getrandom, looping — the kernel only guarantees uninterrupted
 * delivery up to 256 bytes */
static int rand_raw_getrandom(unsigned char *buf, size_t num) {
    size_t left = num;
    while (left > 0) {
        long r = shim_raw_syscall6(SYS_getrandom,
                                   (long)(buf + (num - left)), (long)left,
                                   0, 0, 0, 0);
        if (r == -EINTR) continue;
        if (r <= 0) return 0;
        left -= (size_t)r;
    }
    return 1;
}

static int shim_rand_fill(unsigned char *buf, int num, const char *real,
                          void **cache) {
    if (num < 0) return 0;
    if (!g_shm) {
        static __thread int in_fwd; /* dlsym'd real fn may recurse */
        if (!in_fwd) {
            int (*fn)(unsigned char *, int);
            *(void **)&fn = rand_real(real, cache);
            if (fn) {
                in_fwd = 1;
                int r = fn(buf, num);
                in_fwd = 0;
                return r;
            }
        }
        return rand_raw_getrandom(buf, (size_t)num);
    }
    fill_entropy(buf, (size_t)num);
    return 1;
}

int RAND_bytes(unsigned char *buf, int num) {
    static void *cache;
    return shim_rand_fill(buf, num, "RAND_bytes", &cache);
}

int RAND_priv_bytes(unsigned char *buf, int num) {
    static void *cache;
    return shim_rand_fill(buf, num, "RAND_priv_bytes", &cache);
}

int RAND_pseudo_bytes(unsigned char *buf, int num) {
    static void *cache;
    return shim_rand_fill(buf, num, "RAND_pseudo_bytes", &cache);
}

/* OpenSSL 3's internal TLS path (hello randoms, key generation) calls
 * the _ex API with an explicit library context, NOT the public
 * RAND_bytes symbol — interpose it too or the hole stays open */
static int shim_rand_fill_ex(void *libctx, unsigned char *buf, size_t num,
                             unsigned int strength, const char *real,
                             void **cache) {
    if (g_shm) {
        fill_entropy(buf, num);
        return 1;
    }
    int (*fn)(void *, unsigned char *, size_t, unsigned int);
    *(void **)&fn = rand_real(real, cache);
    if (fn) return fn(libctx, buf, num, strength);
    return rand_raw_getrandom(buf, num);
}

int RAND_bytes_ex(void *libctx, unsigned char *buf, size_t num,
                  unsigned int strength) {
    static void *cache;
    return shim_rand_fill_ex(libctx, buf, num, strength, "RAND_bytes_ex",
                             &cache);
}

int RAND_priv_bytes_ex(void *libctx, unsigned char *buf, size_t num,
                       unsigned int strength) {
    static void *cache;
    return shim_rand_fill_ex(libctx, buf, num, strength,
                             "RAND_priv_bytes_ex", &cache);
}

int RAND_status(void) {
    if (!g_shm) {
        static void *cache;
        int (*fn)(void);
        *(void **)&fn = rand_real("RAND_status", &cache);
        if (fn) return fn();
    }
    return 1;
}

int RAND_poll(void) {
    if (!g_shm) {
        static void *cache;
        int (*fn)(void);
        *(void **)&fn = rand_real("RAND_poll", &cache);
        if (fn) return fn();
    }
    return 1;
}

void RAND_seed(const void *buf, int num) {
    if (!g_shm) {
        static void *cache;
        void (*fn)(const void *, int);
        *(void **)&fn = rand_real("RAND_seed", &cache);
        if (fn) fn(buf, num);
        return;
    }
    (void)buf;
    (void)num; /* deterministic stream: external seeding is a no-op */
}

void RAND_add(const void *buf, int num, double randomness) {
    if (!g_shm) {
        static void *cache;
        void (*fn)(const void *, int, double);
        *(void **)&fn = rand_real("RAND_add", &cache);
        if (fn) fn(buf, num, randomness);
        return;
    }
    (void)buf;
    (void)num;
    (void)randomness;
}

/* ------------------------------------------------------------- sockets */

static int addr_to_ip_port(const struct sockaddr *addr, socklen_t len,
                           uint32_t *ip, uint16_t *port) {
    if (!addr || len < sizeof(struct sockaddr_in) ||
        addr->sa_family != AF_INET) {
        errno = EINVAL;
        return -1;
    }
    const struct sockaddr_in *sin = (const struct sockaddr_in *)addr;
    *ip = sin->sin_addr.s_addr;
    *port = ntohs(sin->sin_port);
    return 0;
}

static void fill_sockaddr(struct sockaddr *addr, socklen_t *alen, uint32_t ip,
                          uint16_t port) {
    if (addr && alen && *alen >= sizeof(struct sockaddr_in)) {
        struct sockaddr_in *sin = (struct sockaddr_in *)addr;
        memset(sin, 0, sizeof(*sin));
        sin->sin_family = AF_INET;
        sin->sin_addr.s_addr = ip;
        sin->sin_port = htons(port);
        *alen = sizeof(struct sockaddr_in);
    }
}

/* Real-fd pipes (command substitution, shell pipelines) connect managed
 * processes that only run when the simulation schedules them: a NATIVE
 * blocking read/write would deadlock the turn.  Poll non-blockingly and
 * yield 1ms of SIMULATED time between attempts — the peer gets turns,
 * the wait costs simulated (not wall) time. */
static void sim_yield_1ms(void) {
    int64_t args[6] = {1000000, 0, 0, 0, 0, 0};
    shim_call(SHIM_OP_NANOSLEEP, args, NULL, 0, NULL, NULL, NULL);
}

/* per-fd fifo-ness cache: 0 unknown, 1 fifo, 2 not — one fstat per fd
 * instead of one per I/O call; close() invalidates */
static uint8_t fd_fifo_cache[SHIM_MAX_FDS];

static int fd_is_fifo(int fd) {
    if (fd < 0 || fd >= SHIM_MAX_FDS) return 0;
    if (fd_fifo_cache[fd] == 0) {
        struct stat st;
        if (fstat(fd, &st) != 0)
            fd_fifo_cache[fd] = 2;
        else if (S_ISFIFO(st.st_mode))
            fd_fifo_cache[fd] = 1;
        else if (S_ISSOCK(st.st_mode))
            /* a real socket under the shim is AF_UNIX/netlink (INET is
             * interposed, INET6 refused): local IPC that must yield
             * simulated time instead of blocking natively */
            fd_fifo_cache[fd] = 1;
        else
            fd_fifo_cache[fd] = 2;
    }
    return fd_fifo_cache[fd] == 1;
}

static int fd_nonblock(int fd) {
    int fl = real_fcntl(fd, F_GETFL, 0);
    return fl >= 0 && (fl & O_NONBLOCK);
}

static void pipe_wait(int fd, short events) {
    for (;;) {
        struct pollfd pfd = {fd, events, 0};
        int r = real_poll(&pfd, 1, 0);
        if (r > 0) return;                      /* ready or hup */
        if (r < 0 && errno != EINTR) return;    /* real error: surface it */
        if (r == 0) sim_yield_1ms();            /* EINTR: just retry */
    }
}

/* the one blocking predicate for real-fd I/O: yield simulated time when
 * the fd is local IPC (pipe/unix socket), the fd is in blocking mode, and
 * the CALL doesn't request non-blocking behavior.  (accept4's flag
 * configures the ACCEPTED socket, not this call's blocking — callers pass
 * dontwait=0 there.) */
static void maybe_yield(int fd, short events, int dontwait) {
    if (g_ready && !dontwait && fd_is_fifo(fd) && !fd_nonblock(fd))
        pipe_wait(fd, events);
}

/* AF_UNIX bytes ride a native socket under engine-scheduled blocking;
 * sizing its kernel buffers from the CONFIG (socket_send_buffer /
 * socket_recv_buffer) makes the backpressure point simulation-controlled
 * instead of a host default — the buffer-accounting half of the
 * reference's unix.rs (its bandwidth model remains native: local IPC is
 * memory-speed there too) */
static void unix_size_buffers(int fd) {
    if (fd < 0 || !g_shm) return;
    /* the kernel DOUBLES setsockopt buffer values (for bookkeeping
     * overhead), so pass half to land the actual backpressure point at
     * the configured size; values below the kernel floor (~4.5 KiB) are
     * clamped by the kernel */
    int v = (int)(g_shm->sock_sndbuf / 2);
    if (v > 0)
        real_setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &v, sizeof(v));
    v = (int)(g_shm->sock_rcvbuf / 2);
    if (v > 0)
        real_setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &v, sizeof(v));
}

int socketpair(int domain, int type, int protocol, int sv[2]) {
    /* raw syscall, NOT libc: this wrapper is reached from the SUD
     * dispatcher too, where a libc call's syscall insn would re-trap */
    long r = shim_raw_syscall6(SYS_socketpair, domain, type, protocol,
                               (long)sv, 0, 0);
    if (r < 0) {
        errno = (int)-r;
        return -1;
    }
    if (g_ready && domain == AF_UNIX) {
        unix_size_buffers(sv[0]);
        unix_size_buffers(sv[1]);
    }
    return 0;
}

int socket(int domain, int type, int protocol) {
    if (!real_socket) resolve_reals();
    int base_type = type & ~(SOCK_NONBLOCK | SOCK_CLOEXEC);
    if (g_ready && domain == AF_UNIX) {
        int fd = real_socket(domain, type, protocol);
        unix_size_buffers(fd);
        return fd;
    }
    if (g_ready && domain == AF_NETLINK && protocol == NETLINK_ROUTE) {
        int fd = reserve_fd();
        if (fd < 0) return -1;
        vfd_kind[fd] = VK_NETLINK;
        vfd_nonblock[fd] = (type & SOCK_NONBLOCK) != 0;
        memset(&nl_state[fd], 0, sizeof(nl_state[fd]));
        return fd;
    }
    if (g_ready && domain == AF_INET6) {
        /* the simulated internet is IPv4; a real IPv6 socket would escape
         * the simulation entirely */
        errno = EAFNOSUPPORT;
        return -1;
    }
    if (!g_ready || domain != AF_INET ||
        (base_type != SOCK_DGRAM && base_type != SOCK_STREAM))
        return real_socket(domain, type, protocol);
    int fd = reserve_fd();
    if (fd < 0) return -1;
    int64_t args[6] = {domain, base_type, fd, 0, 0, 0};
    int64_t ret = shim_call(SHIM_OP_SOCKET, args, NULL, 0, NULL, NULL, NULL);
    if (ret < 0) {
        real_close(fd);
        errno = (int)-ret;
        return -1;
    }
    vfd_register(fd, (type & SOCK_NONBLOCK) != 0,
                 base_type == SOCK_STREAM);
    return fd;
}

int bind(int fd, const struct sockaddr *addr, socklen_t len) {
    if (is_nlfd(fd)) {
        if (addr && len >= sizeof(struct sockaddr_nl)) {
            const struct sockaddr_nl *snl = (const struct sockaddr_nl *)addr;
            nl_state[fd].pid = snl->nl_pid ? snl->nl_pid
                                           : (uint32_t)raw_gettid();
        }
        return 0;
    }
    if (!is_vfd(fd)) return real_bind(fd, addr, len);
    uint32_t ip;
    uint16_t port;
    if (addr_to_ip_port(addr, len, &ip, &port) != 0) return -1;
    int64_t args[6] = {fd, port, 0, 0, 0, 0};
    return (int)ret_errno(
        shim_call(SHIM_OP_BIND, args, NULL, 0, NULL, NULL, NULL));
}

int connect(int fd, const struct sockaddr *addr, socklen_t len) {
    if (!is_vfd(fd)) return real_connect(fd, addr, len);
    uint32_t ip;
    uint16_t port;
    if (addr_to_ip_port(addr, len, &ip, &port) != 0) return -1;
    int64_t args[6] = {fd, (int64_t)ip, port, vfd_nonblock[fd], 0, 0};
    return (int)ret_errno(
        shim_call(SHIM_OP_CONNECT, args, NULL, 0, NULL, NULL, NULL));
}

int listen(int fd, int backlog) {
    if (!is_vfd(fd)) return real_listen(fd, backlog);
    int64_t args[6] = {fd, backlog, 0, 0, 0, 0};
    int64_t ret = shim_call(SHIM_OP_LISTEN, args, NULL, 0, NULL, NULL, NULL);
    if (ret == 0) vfd_listening[fd] = 1;
    return (int)ret_errno(ret);
}

int accept4(int fd, struct sockaddr *addr, socklen_t *alen, int flags) {
    if (!is_vfd(fd)) {
        maybe_yield(fd, POLLIN, 0);
        return real_accept4(fd, addr, alen, flags);
    }
    int child = reserve_fd();
    if (child < 0) return -1;
    int64_t args[6] = {fd, vfd_nonblock[fd], child, 0, 0, 0};
    int64_t reply[6];
    int64_t ret = shim_call(SHIM_OP_ACCEPT, args, NULL, 0, NULL, NULL, reply);
    if (ret < 0) {
        real_close(child);
        errno = (int)-ret;
        return -1;
    }
    vfd_register(child, (flags & SOCK_NONBLOCK) != 0, 1);
    fill_sockaddr(addr, alen, (uint32_t)reply[1], (uint16_t)reply[2]);
    return child;
}

int accept(int fd, struct sockaddr *addr, socklen_t *alen) {
    if (!is_vfd(fd)) {
        maybe_yield(fd, POLLIN, 0);
        return (int)raw_ret(shim_raw_syscall6(SYS_accept, fd, (long)addr,
                                              (long)alen, 0, 0, 0));
    }
    return accept4(fd, addr, alen, 0);
}

/* SHADOW_TPU_NO_ARENA=1 opts large transfers out of the shared arena
 * (falling back to the process_vm MemoryCopier mode) — primarily for
 * exercising that path in tests */
static int arena_enabled(void) {
    static int v = -1;
    if (v < 0) v = getenv("SHADOW_TPU_NO_ARENA") == NULL;
    return v;
}

static ssize_t vfd_sendto(int fd, const void *buf, size_t n, int flags,
                          uint32_t ip, uint16_t port) {
    int nb = vfd_nonblock[fd] || (flags & MSG_DONTWAIT);
    if (!vfd_stream[fd]) {
        if (n > SHIM_PAYLOAD_MAX) { /* larger than any one datagram */
            errno = EMSGSIZE;
            return -1;
        }
        int64_t args[6] = {fd, (int64_t)ip, port, nb, 0, 0};
        return (ssize_t)ret_errno(shim_call(SHIM_OP_SENDTO, args, buf,
                                            (uint32_t)n, NULL, NULL, NULL));
    }
    /* stream, large buffer, preferred path: stage through the channel's
     * shared ARENA — one in-process memcpy, ZERO syscalls, no ptrace
     * dependence (the reference MemoryMapper's capability, re-shaped:
     * the mapping is the per-process channel file both sides hold).
     * SHADOW_TPU_NO_ARENA=1 opts out, leaving the process_vm
     * (MemoryCopier) mode below as the large-transfer path. */
    if (arena_enabled() && n > SHIM_PAYLOAD_MAX) {
        shim_shmem *shm = cur_shm();
        size_t done = 0;
        /* SHIM_ARENA_CHUNK per turn: a nonblocking writer retrying a
         * full buffer must not pay a 1 MiB stage per EAGAIN (same
         * rationale as the direct-memory mode's clamp) */
        while (done < n) {
            size_t chunk = n - done;
            if (chunk > SHIM_ARENA_CHUNK) chunk = SHIM_ARENA_CHUNK;
            memcpy(shm->arena, (const char *)buf + done, chunk);
            int64_t args[6] = {fd, (int64_t)ip, port, nb, SHIM_VM_ARENA,
                               (int64_t)chunk};
            int64_t ret = shim_call(SHIM_OP_SENDTO, args, NULL, 0, NULL,
                                    NULL, NULL);
            if (ret < 0) {
                if (done > 0) return (ssize_t)done;
                errno = (int)-ret;
                return -1;
            }
            done += (size_t)ret;
            if (nb && (size_t)ret < chunk) break; /* buffer full */
        }
        return (ssize_t)done;
    }
    /* (addr, len) direct-memory mode: process_vm_readv — the reference's
     * MemoryCopier — used when the arena is opted out */
    static int g_vmcopy_off;
    if (!g_vmcopy_off && n > SHIM_PAYLOAD_MAX) {
        /* matches the manager's staging clamp exactly: a reply shorter
         * than the request must mean buffer-full (nonblocking partial),
         * never a silent manager-side truncation */
        const size_t VMCHUNK = 256u << 10;
        size_t done = 0;
        while (done < n) {
            size_t chunk = n - done;
            if (chunk > VMCHUNK) chunk = VMCHUNK;
            int64_t args[6] = {fd, (int64_t)ip, port, nb,
                               (int64_t)(uintptr_t)buf + (int64_t)done,
                               (int64_t)chunk};
            int64_t ret = shim_call(SHIM_OP_SENDTO, args, NULL, 0, NULL,
                                    NULL, NULL);
            if (ret == -EOPNOTSUPP && done == 0) {
                g_vmcopy_off = 1;
                break; /* fall back to frame chunking below */
            }
            if (ret < 0) {
                if (done > 0) return (ssize_t)done;
                errno = (int)-ret;
                return -1;
            }
            done += (size_t)ret;
            if (nb && (size_t)ret < chunk) break; /* buffer full */
        }
        if (!g_vmcopy_off) return (ssize_t)done;
    }
    /* stream: the channel carries 64 KiB per hop; loop so a blocking
     * write(fd, buf, len) queues all len bytes like real Linux */
    size_t off = 0;
    do {
        size_t chunk = n - off;
        if (chunk > SHIM_PAYLOAD_MAX) chunk = SHIM_PAYLOAD_MAX;
        int64_t args[6] = {fd, (int64_t)ip, port, nb, 0, 0};
        int64_t ret = shim_call(SHIM_OP_SENDTO, args, (const char *)buf + off,
                                (uint32_t)chunk, NULL, NULL, NULL);
        if (ret < 0) {
            if (off > 0) return (ssize_t)off; /* partial before the error */
            errno = (int)-ret;
            return -1;
        }
        off += (size_t)ret;
        if (nb && (size_t)ret < chunk) break; /* buffer full: partial is fine */
    } while (off < n);
    return (ssize_t)off;
}

static ssize_t vfd_recvfrom(int fd, void *buf, size_t n, int flags,
                            struct sockaddr *addr, socklen_t *alen,
                            int *trunc_out) {
    int nb = vfd_nonblock[fd] || (flags & MSG_DONTWAIT);
    int peek = (flags & MSG_PEEK) != 0;
    int waitall = vfd_stream[fd] && (flags & MSG_WAITALL) && !nb && !peek;
    size_t off = 0;
    if (trunc_out) *trunc_out = 0;
    /* stream, large buffer, consuming read: pass (addr, len) and let the
     * manager copy straight INTO our memory with process_vm_writev (the
     * MemoryCopier's write side) — one exchange per 256 KiB instead of
     * one per 64 KiB frame.  -EOPNOTSUPP on the first try means the
     * kernel forbids cross-process writes: fall back to frames for the
     * process's lifetime, like the send side. */
    /* stream, large consuming read, preferred path: the manager stages
     * the bytes in the channel ARENA and the shim memcpys them out —
     * zero syscalls (see vfd_sendto) */
    if (arena_enabled() && vfd_stream[fd] && !peek && n > SHIM_PAYLOAD_MAX) {
        shim_shmem *shm = cur_shm();
        for (;;) {
            size_t want = n - off;
            if (want > SHIM_ARENA_CHUNK) want = SHIM_ARENA_CHUNK;
            int64_t args[6] = {fd, (int64_t)want, nb, peek, SHIM_VM_ARENA,
                               0};
            int64_t reply[6];
            int64_t ret = shim_call(SHIM_OP_RECVFROM, args, NULL, 0, NULL,
                                    NULL, reply);
            if (ret < 0) {
                if (off > 0) return (ssize_t)off;
                errno = (int)-ret;
                return -1;
            }
            if (off == 0)
                fill_sockaddr(addr, alen, (uint32_t)reply[1],
                              (uint16_t)reply[2]);
            memcpy((char *)buf + off, shm->arena, (size_t)ret);
            off += (size_t)ret;
            if (ret == 0 || off >= n || !waitall) break;
        }
        return (ssize_t)off;
    }
    static int g_vmwrite_off;
    if (!g_vmwrite_off && vfd_stream[fd] && !peek && n > SHIM_PAYLOAD_MAX) {
        const size_t VMCHUNK = 256u << 10;
        for (;;) {
            size_t want = n - off;
            if (want > VMCHUNK) want = VMCHUNK;
            int64_t args[6] = {fd, (int64_t)want, nb, peek,
                               (int64_t)(uintptr_t)buf + (int64_t)off, 0};
            int64_t reply[6];
            int64_t ret = shim_call(SHIM_OP_RECVFROM, args, NULL, 0, NULL,
                                    NULL, reply);
            if (ret == -EOPNOTSUPP && off == 0) {
                g_vmwrite_off = 1;
                break; /* frame path below */
            }
            if (ret < 0) {
                if (off > 0) return (ssize_t)off;
                errno = (int)-ret;
                return -1;
            }
            if (off == 0)
                fill_sockaddr(addr, alen, (uint32_t)reply[1],
                              (uint16_t)reply[2]);
            off += (size_t)ret;
            if (ret == 0 || off >= n || !waitall) break;
        }
        if (!g_vmwrite_off) return (ssize_t)off;
    }
    for (;;) {
        size_t want = n - off;
        if (want > SHIM_PAYLOAD_MAX) want = SHIM_PAYLOAD_MAX;
        int64_t args[6] = {fd, (int64_t)want, nb, peek, 0, 0};
        int64_t reply[6];
        uint32_t got = (uint32_t)want;
        int64_t ret = shim_call(SHIM_OP_RECVFROM, args, NULL, 0,
                                (char *)buf + off, &got, reply);
        if (ret < 0) {
            if (off > 0) return (ssize_t)off;
            errno = (int)-ret;
            return -1;
        }
        if (off == 0) {
            fill_sockaddr(addr, alen, (uint32_t)reply[1], (uint16_t)reply[2]);
            if (trunc_out) *trunc_out = (int)reply[3]; /* datagram cut short */
        }
        off += (size_t)ret;
        /* peek never consumes, so looping would re-read the same bytes */
        if (ret == 0 || off >= n || !waitall || peek) break;
    }
    return (ssize_t)off;
}

/* flatten/scatter helpers for iovec I/O over the single-buffer channel */
#include <sys/uio.h>
#include <limits.h>

/* -1 = invalid set (count out of range or lengths overflow SSIZE_MAX,
 * Linux's EINVAL conditions) */
static ssize_t iov_total(const struct iovec *iov, int cnt) {
    if (cnt < 0 || cnt > IOV_MAX) return -1;
    size_t total = 0;
    for (int i = 0; i < cnt; i++) {
        if (iov[i].iov_len > (size_t)SSIZE_MAX - total) return -1;
        total += iov[i].iov_len;
    }
    return (ssize_t)total;
}

static void iov_gather(const struct iovec *iov, int cnt, char *dst) {
    for (int i = 0; i < cnt; i++) {
        memcpy(dst, iov[i].iov_base, iov[i].iov_len);
        dst += iov[i].iov_len;
    }
}

static void iov_scatter(const struct iovec *iov, int cnt, const char *src,
                        size_t n) {
    for (int i = 0; i < cnt && n; i++) {
        size_t take = iov[i].iov_len < n ? iov[i].iov_len : n;
        memcpy(iov[i].iov_base, src, take);
        src += take;
        n -= take;
    }
}

ssize_t sendto(int fd, const void *buf, size_t n, int flags,
               const struct sockaddr *addr, socklen_t len) {
    if (is_nlfd(fd)) return nl_send(fd, buf, n);
    if (!is_vfd(fd)) {
        maybe_yield(fd, POLLOUT, flags & MSG_DONTWAIT);
        return real_sendto(fd, buf, n, flags, addr, len);
    }
    uint32_t ip = 0;
    uint16_t port = 0;
    if (addr && addr_to_ip_port(addr, len, &ip, &port) != 0) return -1;
    return vfd_sendto(fd, buf, n, flags, ip, port);
}

ssize_t send(int fd, const void *buf, size_t n, int flags) {
    if (is_nlfd(fd)) return nl_send(fd, buf, n);
    if (!is_vfd(fd)) {
        maybe_yield(fd, POLLOUT, flags & MSG_DONTWAIT);
        return (ssize_t)raw_sendto(fd, buf, n, flags, NULL, 0);
    }
    return vfd_sendto(fd, buf, n, flags, 0, 0);
}

ssize_t write(int fd, const void *buf, size_t n) {
    if (is_nlfd(fd)) return nl_send(fd, buf, n);
    if (!is_vfd(fd)) {
        maybe_yield(fd, POLLOUT, 0);
        ssize_t r = real_write(fd, buf, n);
        if (r > 0) meta_note_write(fd);
        return r;
    }
    return vfd_sendto(fd, buf, n, 0, 0, 0);
}

ssize_t recvfrom(int fd, void *buf, size_t n, int flags,
                 struct sockaddr *addr, socklen_t *alen) {
    if (is_nlfd(fd)) return nl_recv(fd, buf, n, flags, addr, alen);
    if (!is_vfd(fd)) {
        maybe_yield(fd, POLLIN, flags & MSG_DONTWAIT);
        return real_recvfrom(fd, buf, n, flags, addr, alen);
    }
    return vfd_recvfrom(fd, buf, n, flags, addr, alen, NULL);
}

ssize_t recv(int fd, void *buf, size_t n, int flags) {
    if (is_nlfd(fd)) return nl_recv(fd, buf, n, flags, NULL, NULL);
    if (!is_vfd(fd)) {
#define real_recv(fd, buf, n, fl) \
    ((ssize_t)raw_recvfrom(fd, buf, n, fl, NULL, NULL))
        int yieldable = g_ready && fd_is_fifo(fd) && !fd_nonblock(fd) &&
                        !(flags & MSG_DONTWAIT);
        int so_type = 0;
        socklen_t so_len = sizeof(so_type);
        int is_stream =
            real_getsockopt(fd, SOL_SOCKET, SO_TYPE, &so_type, &so_len) == 0
            && so_type == SOCK_STREAM;
        if (yieldable && is_stream && (flags & MSG_WAITALL) &&
            !(flags & MSG_PEEK)) {
            /* WAITALL must yield between chunks, not block natively after
             * the first readable byte (PEEK never consumes, so the loop
             * form would duplicate data — PEEK falls through below) */
            size_t off = 0;
            while (off < n) {
                pipe_wait(fd, POLLIN);
                ssize_t r = real_recv(fd, (char *)buf + off, n - off,
                                      flags & ~MSG_WAITALL);
                if (r <= 0) return off > 0 ? (ssize_t)off : r;
                off += (size_t)r;
            }
            return (ssize_t)off;
        }
        if (yieldable) pipe_wait(fd, POLLIN);
        return real_recv(fd, buf, n, flags);
#undef real_recv
    }
    return vfd_recvfrom(fd, buf, n, flags, NULL, NULL, NULL);
}

ssize_t read(int fd, void *buf, size_t n) {
    if (is_nlfd(fd)) return nl_recv(fd, buf, n, 0, NULL, NULL);
    if (!is_vfd(fd)) {
        maybe_yield(fd, POLLIN, 0);
        return real_read(fd, buf, n);
    }
    return vfd_recvfrom(fd, buf, n, 0, NULL, NULL, NULL);
}

int shutdown(int fd, int how) {
    if (!is_vfd(fd)) return real_shutdown(fd, how);
    int64_t args[6] = {fd, how, 0, 0, 0, 0};
    return (int)ret_errno(
        shim_call(SHIM_OP_SHUTDOWN, args, NULL, 0, NULL, NULL, NULL));
}

int close(int fd) {
    if (fd >= 0 && fd < SHIM_MAX_FDS) fd_fifo_cache[fd] = 0;
    fd_meta_reset(fd);
    if (is_nlfd(fd)) {
        memset(&nl_state[fd], 0, sizeof(nl_state[fd]));
        vfd_release(fd);
        return 0;
    }
    if (!is_vfd(fd)) {
        if (g_ready) epoll_forget_fd(fd); /* fd may be an epfd */
        return real_close(fd);
    }
    int64_t args[6] = {fd, 0, 0, 0, 0, 0};
    int64_t ret = shim_call(SHIM_OP_CLOSE, args, NULL, 0, NULL, NULL, NULL);
    vfd_release(fd);
    epoll_forget_fd(fd);
    return (int)ret_errno(ret);
}

static int name_common(int fd, struct sockaddr *addr, socklen_t *alen,
                       uint32_t op) {
    int64_t args[6] = {fd, 0, 0, 0, 0, 0};
    int64_t reply[6];
    int64_t ret = shim_call(op, args, NULL, 0, NULL, NULL, reply);
    if (ret < 0) {
        errno = (int)-ret;
        return -1;
    }
    fill_sockaddr(addr, alen, (uint32_t)reply[1], (uint16_t)reply[2]);
    return 0;
}

int getsockname(int fd, struct sockaddr *addr, socklen_t *alen) {
    if (is_nlfd(fd)) {
        if (addr && alen && *alen >= sizeof(struct sockaddr_nl)) {
            struct sockaddr_nl *snl = (struct sockaddr_nl *)addr;
            memset(snl, 0, sizeof(*snl));
            snl->nl_family = AF_NETLINK;
            snl->nl_pid = nl_state[fd].pid ? nl_state[fd].pid
                                           : (uint32_t)raw_gettid();
            *alen = sizeof(*snl);
        }
        return 0;
    }
    if (!is_vfd(fd)) return real_getsockname(fd, addr, alen);
    return name_common(fd, addr, alen, SHIM_OP_GETSOCKNAME);
}

int getpeername(int fd, struct sockaddr *addr, socklen_t *alen) {
    if (!is_vfd(fd)) return real_getpeername(fd, addr, alen);
    return name_common(fd, addr, alen, SHIM_OP_GETPEERNAME);
}

int setsockopt(int fd, int level, int optname, const void *optval,
               socklen_t optlen) {
    if (is_nlfd(fd)) return 0; /* SNDBUF/RCVBUF etc.: accept and ignore */
    if (!is_vfd(fd)) return real_setsockopt(fd, level, optname, optval, optlen);
    (void)level;
    (void)optname;
    (void)optval;
    (void)optlen;
    return 0; /* accept and ignore: buffers/REUSEADDR/NODELAY are simulated */
}

int getsockopt(int fd, int level, int optname, void *optval, socklen_t *optlen) {
    if (!is_vfd(fd)) return real_getsockopt(fd, level, optname, optval, optlen);
    if (level == SOL_SOCKET && optname == SO_ERROR) {
        int64_t args[6] = {fd, 0, 0, 0, 0, 0};
        int64_t reply[6];
        int64_t ret =
            shim_call(SHIM_OP_SOCKERR, args, NULL, 0, NULL, NULL, reply);
        if (ret < 0) {
            errno = (int)-ret;
            return -1;
        }
        if (optval && optlen && *optlen >= sizeof(int)) {
            *(int *)optval = (int)reply[1];
            *optlen = sizeof(int);
        }
        return 0;
    }
    int value;
    if (level == SOL_SOCKET) {
        switch (optname) {
            case SO_LINGER:   /* struct-valued: zeroed = disabled/none */
            case SO_RCVTIMEO:
            case SO_SNDTIMEO: {
                if (optval && optlen) {
                    size_t want = optname == SO_LINGER
                                      ? sizeof(struct linger)
                                      : sizeof(struct timeval);
                    size_t n = *optlen < want ? *optlen : want;
                    memset(optval, 0, n);
                    *optlen = (socklen_t)n;
                }
                return 0;
            }
            case SO_SNDBUF: value = (int)g_shm->sock_sndbuf; break;
            case SO_RCVBUF: value = (int)g_shm->sock_rcvbuf; break;
            case SO_TYPE:
                value = vfd_stream[fd] ? SOCK_STREAM : SOCK_DGRAM;
                break;
            case SO_DOMAIN: value = AF_INET; break;
            case SO_PROTOCOL:
                value = vfd_stream[fd] ? IPPROTO_TCP : IPPROTO_UDP;
                break;
            case SO_ACCEPTCONN: value = vfd_listening[fd]; break;
            case SO_REUSEADDR:
            case SO_KEEPALIVE:
            case SO_BROADCAST: value = 0; break;
            default:
                errno = ENOPROTOOPT;
                return -1;
        }
    } else if (level == IPPROTO_TCP) {
        value = 0; /* TCP_NODELAY etc: accepted as off */
    } else {
        errno = ENOPROTOOPT;
        return -1;
    }
    if (optval && optlen && *optlen >= sizeof(int)) {
        *(int *)optval = value;
        *optlen = sizeof(int);
    }
    return 0;
}

int fcntl(int fd, int cmd, ...) {
    va_list ap;
    va_start(ap, cmd);
    void *arg = va_arg(ap, void *);
    va_end(ap);
    if (!is_vfd(fd) && !is_nlfd(fd)) return real_fcntl(fd, cmd, arg);
    switch (cmd) {
        case F_GETFL:
            return O_RDWR | (vfd_nonblock[fd] ? O_NONBLOCK : 0);
        case F_SETFL:
            vfd_nonblock[fd] = (((intptr_t)arg) & O_NONBLOCK) != 0;
            return 0;
        case F_GETFD:
            return 0;
        case F_SETFD:
            return 0;
        default:
            errno = EINVAL;
            return -1;
    }
}

int ioctl(int fd, unsigned long req, ...) {
    va_list ap;
    va_start(ap, req);
    void *arg = va_arg(ap, void *);
    va_end(ap);
    if (!is_vfd(fd)) return real_ioctl(fd, req, arg);
    if (req == FIONBIO) {
        vfd_nonblock[fd] = arg && *(int *)arg != 0;
        return 0;
    }
    if (req == FIONREAD) {
        int64_t args[6] = {fd, 0, 0, 0, 0, 0};
        int64_t reply[6];
        int64_t ret =
            shim_call(SHIM_OP_FIONREAD, args, NULL, 0, NULL, NULL, reply);
        if (ret < 0) {
            errno = (int)-ret;
            return -1;
        }
        if (arg) *(int *)arg = (int)reply[1];
        return 0;
    }
    errno = EINVAL;
    return -1;
}

/* ----------------------------------------------------------- readiness */

/* Wait-scoped sigmask (ppoll/pselect6/epoll_pwait): the atomic
 * unmask-and-wait these calls exist for.  Entering swaps BOTH the real
 * kernel mask (so a pending signal unblocked by the wait mask fires at
 * shim_call's mask restore, running its handler BEFORE the wait returns
 * EINTR) and the manager-visible blocked_signals mirror (so the manager
 * releases the park for a signal the wait mask admits).  SIGSYS is
 * stripped (a blocked SIGSYS turns the next dispatch into a forced
 * kill). */
typedef struct {
    uint64_t saved_real;
    uint64_t saved_pub;
    int active;
} wait_mask_t;

static void wait_mask_enter(const void *umask, size_t ssz, wait_mask_t *w) {
    w->active = 0;
    if (!umask || ssz < 8) return;
    uint64_t m;
    memcpy(&m, umask, 8);
    m &= ~(1ull << (SIGSYS - 1));
    shim_raw_syscall6(SYS_rt_sigprocmask, SIG_SETMASK, (long)&m,
                      (long)&w->saved_real, 8, 0, 0);
    shim_shmem *shm = cur_shm();
    if (shm) {
        w->saved_pub = __atomic_load_n(&shm->blocked_signals,
                                       __ATOMIC_RELAXED);
        __atomic_store_n(&shm->blocked_signals, m, __ATOMIC_RELAXED);
    }
    w->active = 1;
}

static void wait_mask_leave(wait_mask_t *w) {
    if (!w->active) return;
    int saved_errno = errno; /* the wait's errno (EINTR) must survive */
    shim_raw_syscall6(SYS_rt_sigprocmask, SIG_SETMASK, (long)&w->saved_real,
                      0, 8, 0, 0);
    shim_shmem *shm = cur_shm();
    if (shm)
        __atomic_store_n(&shm->blocked_signals, w->saved_pub,
                         __ATOMIC_RELAXED);
    errno = saved_errno;
}

/* One manager round-trip evaluating readiness of simulated fds; parks the
 * plugin until an fd is ready or the (simulated) timeout elapses. */
static int shim_poll_call(shim_pollfd *entries, int n, int64_t timeout_ns,
                          uint32_t *revents_out) {
    int64_t args[6] = {n, timeout_ns, 0, 0, 0, 0};
    uint32_t in_len = (uint32_t)(n * sizeof(uint32_t));
    int64_t ret = shim_call(SHIM_OP_POLL, args, entries,
                            (uint32_t)(n * sizeof(shim_pollfd)), revents_out,
                            &in_len, NULL);
    return (int)ret_errno(ret);
}

static int poll_ns(struct pollfd *fds, nfds_t nfds, int64_t timeout_ns) {
    if (!real_socket) resolve_reals();
    /* netlink fds are synchronous (request/answer in the shim): report
     * readiness immediately — readable iff a reply is queued */
    int nl_ready = 0, any_nl = 0;
    for (nfds_t i = 0; i < nfds; i++) {
        if (!is_nlfd(fds[i].fd)) continue;
        any_nl = 1;
        short rev = 0;
        shim_nl_state *st = &nl_state[fds[i].fd];
        if ((fds[i].events & POLLIN) && (st->pending || st->ack))
            rev |= POLLIN;
        if (fds[i].events & POLLOUT) rev |= POLLOUT;
        fds[i].revents = rev;
        if (rev) nl_ready++;
    }
    if (nl_ready) {
        for (nfds_t i = 0; i < nfds; i++)
            if (!is_nlfd(fds[i].fd)) fds[i].revents = 0;
        return nl_ready;
    }
    int any_virtual = 0, any_real = 0;
    for (nfds_t i = 0; i < nfds; i++) {
        if (is_vfd(fds[i].fd))
            any_virtual = 1;
        else if (!is_nlfd(fds[i].fd))
            any_real = 1;
    }
    if (any_nl && !any_virtual && !any_real) {
        /* idle emulated netlink fd(s) only: nothing can arrive without a
         * request in flight (multicast group notifications are not
         * emulated) — park in SIMULATED time instead of real_poll()ing
         * the O_PATH reservation, which reports always-ready and would
         * hot-spin the wall clock */
        for (nfds_t i = 0; i < nfds; i++) fds[i].revents = 0;
        uint32_t rv;
        int ready = shim_poll_call(NULL, 0, timeout_ns, &rv);
        return ready < 0 ? -1 : 0;
    }
    if (!any_virtual) {
        if (timeout_ns < 0) /* intentional forever-block on real fds */
            return real_poll(fds, nfds, -1);
        if (timeout_ns == 0) /* non-blocking probe: no wall block possible */
            return real_poll(fds, nfds, 0);
        /* poll-as-sleep (nfds==0) or real-only sets with a timeout: park
         * in SIMULATED time so the rest of the simulation keeps running */
        if (any_real) {
            static int warned;
            if (!warned++)
                shim_warn("timed poll() on real fds sleeps in simulated "
                          "time; real fds report no events");
        }
        for (nfds_t i = 0; i < nfds; i++) fds[i].revents = 0;
        uint32_t rv;
        int ready = shim_poll_call(NULL, 0, timeout_ns, &rv);
        return ready < 0 ? -1 : 0;
    }
    if (any_real) {
        static int warned;
        if (!warned++)
            shim_warn("poll() mixing real and simulated fds: real fds "
                      "report no events");
    }
    if (nfds > 1024) {
        errno = EINVAL;
        return -1;
    }
    shim_pollfd entries[1024];
    uint32_t revents[1024];
    int n = 0;
    for (nfds_t i = 0; i < nfds; i++) {
        fds[i].revents = 0;
        if (!is_vfd(fds[i].fd)) continue;
        entries[n].fd = fds[i].fd;
        entries[n].events = (uint32_t)fds[i].events;
        n++;
    }
    int ready = shim_poll_call(entries, n, timeout_ns, revents);
    if (ready < 0) return -1;
    int j = 0, total = 0;
    for (nfds_t i = 0; i < nfds; i++) {
        if (!is_vfd(fds[i].fd)) continue;
        fds[i].revents = (short)revents[j++];
        if (fds[i].revents) total++;
    }
    return total;
}

int poll(struct pollfd *fds, nfds_t nfds, int timeout) {
    if (!real_socket) resolve_reals();
    if (!g_ready) return real_poll(fds, nfds, timeout);
    return poll_ns(fds, nfds,
                   timeout < 0 ? -1 : (int64_t)timeout * 1000000ll);
}

int ppoll(struct pollfd *fds, nfds_t nfds, const struct timespec *ts,
          const sigset_t *mask) {
    if (!g_ready) {
        static int (*rp)(struct pollfd *, nfds_t, const struct timespec *,
                         const sigset_t *);
        if (!rp) rp = dlsym(RTLD_NEXT, "ppoll");
        return rp(fds, nfds, ts, mask);
    }
    /* full ns precision: a 0.5 ms wait must advance simulated time, not
     * degrade into a same-instant spin */
    int64_t timeout_ns =
        ts ? (int64_t)ts->tv_sec * 1000000000ll + ts->tv_nsec : -1;
    wait_mask_t w;
    wait_mask_enter(mask, mask ? 8 : 0, &w);
    int r = poll_ns(fds, nfds, timeout_ns);
    wait_mask_leave(&w);
    return r;
}

int select(int nfds, fd_set *rd, fd_set *wr, fd_set *ex, struct timeval *tv) {
    if (!real_socket) resolve_reals();
    if (!g_ready) return real_select(nfds, rd, wr, ex, tv);
    int any_virtual = 0, any_real = 0;
    for (int fd = 0; fd < nfds && fd < FD_SETSIZE; fd++) {
        int in_any = (rd && FD_ISSET(fd, rd)) || (wr && FD_ISSET(fd, wr)) ||
                     (ex && FD_ISSET(fd, ex));
        if (!in_any) continue;
        if (is_vfd(fd))
            any_virtual = 1;
        else
            any_real = 1;
    }
    if (!any_virtual) {
        int64_t tns = tv ? (int64_t)tv->tv_sec * 1000000000ll +
                               (int64_t)tv->tv_usec * 1000ll
                         : -1;
        if (tns <= 0) return real_select(nfds, rd, wr, ex, tv);
        if (any_real) {
            static int warned2;
            if (!warned2++)
                shim_warn("timed select() on real fds sleeps in simulated "
                          "time; real fds report no events");
        }
        if (rd) FD_ZERO(rd);
        if (wr) FD_ZERO(wr);
        if (ex) FD_ZERO(ex);
        uint32_t rv;
        int ready = shim_poll_call(NULL, 0, tns, &rv);
        return ready < 0 ? -1 : 0;
    }
    if (any_real) {
        static int warned;
        if (!warned++)
            shim_warn("select() mixing real and simulated fds: real fds "
                      "report no events");
    }
    shim_pollfd entries[1024];
    uint32_t revents[1024];
    int n = 0;
    for (int fd = 0; fd < nfds && fd < FD_SETSIZE; fd++) {
        if (!is_vfd(fd)) continue;
        if (n >= 1024) {
            errno = EINVAL;
            return -1;
        }
        uint32_t ev = 0;
        if (rd && FD_ISSET(fd, rd)) ev |= SHIM_POLLIN;
        if (wr && FD_ISSET(fd, wr)) ev |= SHIM_POLLOUT;
        if (ex && FD_ISSET(fd, ex)) ev |= SHIM_POLLERR;
        if (!ev) continue;
        entries[n].fd = fd;
        entries[n].events = ev;
        n++;
    }
    int64_t timeout_ns =
        tv ? (int64_t)tv->tv_sec * 1000000000ll + (int64_t)tv->tv_usec * 1000ll
           : -1;
    int ready = shim_poll_call(entries, n, timeout_ns, revents);
    if (ready < 0) return -1;
    if (rd) FD_ZERO(rd);
    if (wr) FD_ZERO(wr);
    if (ex) FD_ZERO(ex);
    int total = 0;
    for (int i = 0; i < n; i++) {
        uint32_t rev = revents[i];
        int fd = entries[i].fd;
        /* select semantics: error conditions mark the fd readable+writable */
        if (rd && (rev & (SHIM_POLLIN | SHIM_POLLERR | SHIM_POLLHUP)) &&
            (entries[i].events & SHIM_POLLIN)) {
            FD_SET(fd, rd);
            total++;
        }
        if (wr && (rev & (SHIM_POLLOUT | SHIM_POLLERR)) &&
            (entries[i].events & SHIM_POLLOUT)) {
            FD_SET(fd, wr);
            total++;
        }
        if (ex && (rev & SHIM_POLLERR) && (entries[i].events & SHIM_POLLERR)) {
            FD_SET(fd, ex);
            total++;
        }
    }
    return total;
}

/* ------------------------------------------------------------- epoll */

int epoll_ctl(int epfd, int op, int fd, struct epoll_event *event) {
    if (!real_socket) resolve_reals();
    if (!g_ready || !is_vfd(fd)) {
        if (g_ready && op == EPOLL_CTL_ADD && epfd >= 0 && epfd < SHIM_MAX_FDS)
            epoll_has_real[epfd] = 1;
        return real_epoll_ctl(epfd, op, fd, event);
    }
    if (epfd < 0 || epfd >= SHIM_MAX_FDS) {
        errno = EBADF;
        return -1;
    }
    if (!epoll_regs[epfd]) {
        epoll_regs[epfd] = calloc(EPOLL_MAX_REGS, sizeof(epoll_reg));
        if (!epoll_regs[epfd]) {
            errno = ENOMEM;
            return -1;
        }
    }
    epoll_reg *regs = epoll_regs[epfd];
    int n = epoll_nregs[epfd];
    int idx = -1;
    for (int i = 0; i < n; i++)
        if (regs[i].fd == fd) idx = i;
    switch (op) {
        case EPOLL_CTL_ADD:
            if (idx >= 0) {
                errno = EEXIST;
                return -1;
            }
            if (n >= EPOLL_MAX_REGS) {
                cap_warn(1, "epoll registration table (EPOLL_MAX_REGS)",
                         EPOLL_MAX_REGS);
                errno = ENOSPC;
                return -1;
            }
            regs[n].fd = fd;
            regs[n].events = event->events;
            regs[n].data = event->data.u64;
            epoll_nregs[epfd] = n + 1;
            return 0;
        case EPOLL_CTL_MOD:
            if (idx < 0) {
                errno = ENOENT;
                return -1;
            }
            regs[idx].events = event->events;
            regs[idx].data = event->data.u64;
            return 0;
        case EPOLL_CTL_DEL:
            if (idx < 0) {
                errno = ENOENT;
                return -1;
            }
            regs[idx] = regs[n - 1];
            epoll_nregs[epfd] = n - 1;
            return 0;
        default:
            errno = EINVAL;
            return -1;
    }
}

int epoll_wait(int epfd, struct epoll_event *events, int maxevents,
               int timeout) {
    if (!real_socket) resolve_reals();
    if (!g_ready) return real_epoll_wait(epfd, events, maxevents, timeout);
    int n = (epfd >= 0 && epfd < SHIM_MAX_FDS) ? epoll_nregs[epfd] : 0;
    if (n == 0) {
        /* no simulated registrations: epolls carrying real fds keep real
         * semantics; an EMPTY epoll with a timeout is a sleep and must
         * advance simulated time */
        if (timeout < 0 || epfd < 0 || epfd >= SHIM_MAX_FDS ||
            epoll_has_real[epfd])
            return real_epoll_wait(epfd, events, maxevents, timeout);
        uint32_t rv;
        int ready = shim_poll_call(NULL, 0, (int64_t)timeout * 1000000ll, &rv);
        return ready < 0 ? -1 : 0;
    }
    if (epoll_has_real[epfd]) {
        static int warned;
        if (!warned++)
            shim_warn("epoll mixing real and simulated fds: real fds "
                      "report no events");
    }
    epoll_reg *regs = epoll_regs[epfd];
    static shim_pollfd entries[EPOLL_MAX_REGS]; /* too big for the stack */
    static uint32_t revents[EPOLL_MAX_REGS];
    for (int i = 0; i < n; i++) {
        entries[i].fd = regs[i].fd;
        uint32_t ev = 0;
        if (regs[i].events & EPOLLIN) ev |= SHIM_POLLIN;
        if (regs[i].events & EPOLLOUT) ev |= SHIM_POLLOUT;
        entries[i].events = ev;
    }
    int64_t timeout_ns = timeout < 0 ? -1 : (int64_t)timeout * 1000000ll;
    int ready = shim_poll_call(entries, n, timeout_ns, revents);
    if (ready < 0) return -1;
    int out = 0;
    for (int i = 0; i < n && out < maxevents; i++) {
        if (!revents[i]) continue;
        uint32_t ev = 0;
        if (revents[i] & SHIM_POLLIN) ev |= EPOLLIN;
        if (revents[i] & SHIM_POLLOUT) ev |= EPOLLOUT;
        if (revents[i] & SHIM_POLLERR) ev |= EPOLLERR;
        if (revents[i] & SHIM_POLLHUP) ev |= EPOLLHUP;
        events[out].events = ev;
        events[out].data.u64 = regs[i].data;
        out++;
    }
    return out;
}

int epoll_pwait(int epfd, struct epoll_event *events, int maxevents,
                int timeout, const sigset_t *mask) {
    if (!g_ready) {
        static int (*rp)(int, struct epoll_event *, int, int,
                         const sigset_t *);
        if (!rp) rp = dlsym(RTLD_NEXT, "epoll_pwait");
        return rp(epfd, events, maxevents, timeout, mask);
    }
    wait_mask_t w;
    wait_mask_enter(mask, mask ? 8 : 0, &w);
    int r = epoll_wait(epfd, events, maxevents, timeout);
    wait_mask_leave(&w);
    return r;
}

int pselect(int nfds, fd_set *rd, fd_set *wr, fd_set *ex,
            const struct timespec *ts, const sigset_t *mask) {
    if (!g_ready) {
        static int (*rp)(int, fd_set *, fd_set *, fd_set *,
                         const struct timespec *, const sigset_t *);
        if (!rp) rp = dlsym(RTLD_NEXT, "pselect");
        return rp(nfds, rd, wr, ex, ts, mask);
    }
    struct timeval tv, *tvp = NULL;
    if (ts) {
        tv.tv_sec = ts->tv_sec;
        tv.tv_usec = (ts->tv_nsec + 999) / 1000;
        if (tv.tv_usec >= 1000000) { /* nsec > 999999000 rounds up a sec */
            tv.tv_sec += 1;
            tv.tv_usec = 0;
        }
        tvp = &tv;
    }
    wait_mask_t w;
    wait_mask_enter(mask, mask ? 8 : 0, &w);
    int r = select(nfds, rd, wr, ex, tvp);
    wait_mask_leave(&w);
    return r;
}

/* ----------------------------------------------- timerfd / eventfd.
 * Real timerfds tick WALL time — useless under a simulated clock — and a
 * blocking eventfd read would stall the turn.  Both become manager-side
 * virtual fds on the simulated clock (the reference's
 * descriptor/timerfd.rs / eventfd.rs); read/write/poll/close reuse the
 * generic fd ops via kind dispatch. */
#include <sys/eventfd.h>
#include <sys/timerfd.h>

static int64_t ts_to_ns(const struct timespec *ts) {
    return (int64_t)ts->tv_sec * 1000000000ll + ts->tv_nsec;
}

static void ns_to_ts(int64_t ns, struct timespec *ts) {
    ts->tv_sec = ns / 1000000000ll;
    ts->tv_nsec = ns % 1000000000ll;
}

/* ---- inotify: manager-side stub fds (the reference fork's minimal
 * inotify stubs, handler/inotify.rs).  Real inotify would watch the REAL
 * filesystem asynchronously — nondeterministic under the simulation — so
 * watches succeed and are tracked, but no event ever fires: reads block
 * in simulated time (EAGAIN when nonblocking), poll reports no
 * readiness.  Apps that merely register watches keep working. */

#include <sys/inotify.h>

int inotify_init1(int flags) {
    if (!g_ready)
        return (int)raw_ret(
            shim_raw_syscall6(SYS_inotify_init1, flags, 0, 0, 0, 0, 0));
    if (flags & ~(IN_NONBLOCK | IN_CLOEXEC)) { /* kernel contract */
        errno = EINVAL;
        return -1;
    }
    int fd = reserve_fd();
    if (fd < 0) return -1;
    int64_t args[6] = {fd, 0, 0, 0, 0, 0};
    int64_t ret =
        shim_call(SHIM_OP_INOTIFY_CREATE, args, NULL, 0, NULL, NULL, NULL);
    if (ret < 0) {
        real_close(fd);
        errno = (int)-ret;
        return -1;
    }
    vfd_register(fd, (flags & IN_NONBLOCK) != 0, 0);
    if (flags & IN_CLOEXEC) /* honored on the backing fd: exec closes it */
        shim_raw_syscall6(SYS_fcntl, fd, F_SETFD, FD_CLOEXEC, 0, 0, 0);
    return fd;
}

int inotify_init(void) { return inotify_init1(0); }

int inotify_add_watch(int fd, const char *pathname, uint32_t mask) {
    if (!is_vfd(fd))
        return (int)raw_ret(shim_raw_syscall6(
            SYS_inotify_add_watch, fd, (long)pathname, mask, 0, 0, 0));
    int64_t args[6] = {fd, (int64_t)mask, 0, 0, 0, 0};
    int64_t ret = shim_call(SHIM_OP_INOTIFY_ADD, args, pathname,
                            (uint32_t)strlen(pathname), NULL, NULL, NULL);
    return (int)ret_errno(ret);
}

int inotify_rm_watch(int fd, int wd) {
    if (!is_vfd(fd))
        return (int)raw_ret(shim_raw_syscall6(SYS_inotify_rm_watch, fd, wd,
                                              0, 0, 0, 0));
    int64_t args[6] = {fd, wd, 0, 0, 0, 0};
    int64_t ret =
        shim_call(SHIM_OP_INOTIFY_RM, args, NULL, 0, NULL, NULL, NULL);
    return (int)ret_errno(ret);
}

int timerfd_create(int clockid, int flags) {
    if (!g_ready) return (int)raw_timerfd_create(clockid, flags);
    (void)clockid; /* every clock is the one simulated clock */
    int fd = reserve_fd();
    if (fd < 0) return -1;
    int64_t args[6] = {fd, 0, 0, 0, 0, 0};
    int64_t ret =
        shim_call(SHIM_OP_TIMERFD_CREATE, args, NULL, 0, NULL, NULL, NULL);
    if (ret < 0) {
        real_close(fd);
        errno = (int)-ret;
        return -1;
    }
    vfd_register(fd, (flags & TFD_NONBLOCK) != 0, 0);
    return fd;
}

int timerfd_settime(int fd, int flags, const struct itimerspec *new_value,
                    struct itimerspec *old_value) {
    if (!is_vfd(fd))
        return (int)raw_timerfd_settime(fd, flags, new_value, old_value);
    if (!new_value) {
        errno = EFAULT;
        return -1;
    }
    int64_t initial = ts_to_ns(&new_value->it_value);
    int is_abs = 0;
    if (initial && (flags & TFD_TIMER_ABSTIME)) {
        /* manager takes relative ns; an overdue value may go <= 0 — the
         * manager then counts the missed expirations and keeps later
         * ticks on the absolute grid, as Linux does */
        initial -= (int64_t)sim_now_ns();
        is_abs = 1;
    }
    int64_t args[6] = {fd, initial, ts_to_ns(&new_value->it_interval),
                       is_abs, 0, 0};
    int64_t reply[6];
    int64_t ret =
        shim_call(SHIM_OP_TIMERFD_SETTIME, args, NULL, 0, NULL, NULL, reply);
    if (ret < 0) {
        errno = (int)-ret;
        return -1;
    }
    if (old_value) {
        ns_to_ts(reply[1], &old_value->it_value);
        ns_to_ts(reply[2], &old_value->it_interval);
    }
    return 0;
}

int timerfd_gettime(int fd, struct itimerspec *curr) {
    if (!is_vfd(fd)) return (int)raw_timerfd_gettime(fd, curr);
    int64_t args[6] = {fd, 0, 0, 0, 0, 0};
    int64_t reply[6];
    int64_t ret =
        shim_call(SHIM_OP_TIMERFD_GETTIME, args, NULL, 0, NULL, NULL, reply);
    if (ret < 0) {
        errno = (int)-ret;
        return -1;
    }
    if (curr) {
        ns_to_ts(reply[1], &curr->it_value);
        ns_to_ts(reply[2], &curr->it_interval);
    }
    return 0;
}

int eventfd(unsigned int initval, int flags) {
    if (!g_ready) return (int)raw_eventfd2(initval, flags);
    int fd = reserve_fd();
    if (fd < 0) return -1;
    int64_t args[6] = {fd, initval, (flags & EFD_SEMAPHORE) != 0, 0, 0, 0};
    int64_t ret =
        shim_call(SHIM_OP_EVENTFD_CREATE, args, NULL, 0, NULL, NULL, NULL);
    if (ret < 0) {
        real_close(fd);
        errno = (int)-ret;
        return -1;
    }
    vfd_register(fd, (flags & EFD_NONBLOCK) != 0, 0);
    return fd;
}

/* glibc's helpers resolve read/write internally; route them through the
 * interposed fd ops so simulated eventfds work */
int eventfd_read(int fd, eventfd_t *value) {
    return read(fd, value, sizeof(*value)) == sizeof(*value) ? 0 : -1;
}

int eventfd_write(int fd, eventfd_t value) {
    return write(fd, &value, sizeof(value)) == sizeof(value) ? 0 : -1;
}

/* ----------------------------------------------------- name resolution */

/* getaddrinfo against the simulation's hosts file — the reference
 * implements getaddrinfo in its libc preload against shadow's DNS
 * (preload-libc shim_api_addrinfo.c, dns.rs:130-190).  The manager passes
 * the /etc/hosts-style file in SHADOW_TPU_HOSTS_FILE; lookups are local
 * (no channel hop) and deterministic.  Numeric-only service strings. */
#include <netdb.h>

static int hosts_lookup(const char *name, uint32_t *ip_out) {
    const char *path = getenv("SHADOW_TPU_HOSTS_FILE");
    if (!path) return -1;
    FILE *f = fopen(path, "re");
    if (!f) return -1;
    char line[512];
    int found = -1;
    while (fgets(line, sizeof(line), f)) {
        char ip[64], host[256];
        if (sscanf(line, "%63s %255s", ip, host) != 2) continue;
        if (strcmp(host, name) != 0) continue;
        struct in_addr a;
        if (inet_pton(AF_INET, ip, &a) == 1) {
            *ip_out = a.s_addr;
            found = 0;
        }
        break;
    }
    fclose(f);
    return found;
}

int getaddrinfo(const char *node, const char *service,
                const struct addrinfo *hints, struct addrinfo **res) {
    if (!real_socket) resolve_reals();
    static int (*real_gai)(const char *, const char *,
                           const struct addrinfo *, struct addrinfo **);
    if (!real_gai) real_gai = dlsym(RTLD_NEXT, "getaddrinfo");
    if (!g_ready) return real_gai(node, service, hints, res);

    if (hints && hints->ai_family != AF_UNSPEC && hints->ai_family != AF_INET)
        return EAI_FAMILY; /* the simulated internet is IPv4 */

    uint32_t ip;
    if (node == NULL) {
        ip = (hints && (hints->ai_flags & AI_PASSIVE)) ? INADDR_ANY
                                                       : htonl(INADDR_LOOPBACK);
    } else {
        struct in_addr a;
        if (inet_pton(AF_INET, node, &a) == 1) {
            ip = a.s_addr;
        } else if (hints && (hints->ai_flags & AI_NUMERICHOST)) {
            return EAI_NONAME;
        } else if (hosts_lookup(node, &ip) != 0) {
            return EAI_NONAME;
        }
    }
    long port = 0;
    if (service) {
        char *end;
        port = strtol(service, &end, 10);
        if (*end != '\0' || port < 0 || port > 65535) return EAI_SERVICE;
    }

    int socktype = hints && hints->ai_socktype ? hints->ai_socktype : SOCK_STREAM;
    const char *canon = node ? node : "localhost";
    size_t canon_len =
        (hints && (hints->ai_flags & AI_CANONNAME)) ? strlen(canon) + 1 : 0;
    struct addrinfo *ai =
        calloc(1, sizeof(*ai) + sizeof(struct sockaddr_in) + canon_len);
    if (!ai) return EAI_MEMORY;
    struct sockaddr_in *sin = (struct sockaddr_in *)(ai + 1);
    sin->sin_family = AF_INET;
    sin->sin_addr.s_addr = ip;
    sin->sin_port = htons((uint16_t)port);
    ai->ai_family = AF_INET;
    ai->ai_socktype = socktype;
    ai->ai_protocol = socktype == SOCK_DGRAM ? IPPROTO_UDP : IPPROTO_TCP;
    ai->ai_addrlen = sizeof(struct sockaddr_in);
    ai->ai_addr = (struct sockaddr *)sin;
    if (canon_len) {
        char *cn = (char *)(sin + 1);
        memcpy(cn, canon, canon_len);
        ai->ai_canonname = cn;
    }
    *res = ai;
    return 0;
}

void freeaddrinfo(struct addrinfo *res) {
    if (!g_ready) {
        static void (*real_fai)(struct addrinfo *);
        if (!real_fai) real_fai = dlsym(RTLD_NEXT, "freeaddrinfo");
        real_fai(res);
        return;
    }
    while (res) {
        struct addrinfo *next = res->ai_next;
        free(res); /* sockaddr is co-allocated */
        res = next;
    }
}

struct hostent *gethostbyname(const char *name) {
    if (!real_socket) resolve_reals();
    static struct hostent *(*real_ghn)(const char *);
    if (!real_ghn) real_ghn = dlsym(RTLD_NEXT, "gethostbyname");
    if (!g_ready) return real_ghn(name);

    static struct in_addr addr;
    static char *addr_list[2];
    static char hname[256];
    static struct hostent he;
    uint32_t ip;
    struct in_addr a;
    if (inet_pton(AF_INET, name, &a) == 1) {
        ip = a.s_addr;
    } else if (hosts_lookup(name, &ip) != 0) {
        h_errno = HOST_NOT_FOUND;
        return NULL;
    }
    addr.s_addr = ip;
    addr_list[0] = (char *)&addr;
    addr_list[1] = NULL;
    snprintf(hname, sizeof(hname), "%s", name);
    he.h_name = hname;
    he.h_aliases = addr_list + 1; /* empty list */
    he.h_addrtype = AF_INET;
    he.h_length = sizeof(struct in_addr);
    he.h_addr_list = addr_list;
    return &he;
}

/* Reverse lookup against the simulated hosts file — without it, glibc's
 * gethostbyaddr fires real resolver UDP queries at /etc/resolv.conf's
 * nameserver through the simulated network (CPython's http.server calls
 * socket.getfqdn at startup, for example).  Unknown addresses fail fast
 * and locally. */
static int hosts_reverse(uint32_t ip, char *name_out, size_t cap) {
    const char *path = getenv("SHADOW_TPU_HOSTS_FILE");
    if (!path) return -1;
    FILE *f = fopen(path, "re");
    if (!f) return -1;
    char line[512];
    int found = -1;
    while (fgets(line, sizeof(line), f)) {
        char ipstr[64], host[256];
        if (sscanf(line, "%63s %255s", ipstr, host) != 2) continue;
        struct in_addr a;
        if (inet_pton(AF_INET, ipstr, &a) == 1 && a.s_addr == ip) {
            snprintf(name_out, cap, "%s", host);
            found = 0;
            break;
        }
    }
    fclose(f);
    return found;
}

struct hostent *gethostbyaddr(const void *addr, socklen_t len, int type) {
    if (!real_socket) resolve_reals();
    static struct hostent *(*real_gha)(const void *, socklen_t, int);
    if (!real_gha) *(void **)&real_gha = dlsym(RTLD_NEXT, "gethostbyaddr");
    if (!g_ready) return real_gha(addr, len, type);
    static struct in_addr ra;
    static char *ra_list[2];
    static char rname[256];
    static struct hostent rhe;
    if (type != AF_INET || len < sizeof(struct in_addr) || !addr) {
        h_errno = HOST_NOT_FOUND;
        return NULL;
    }
    uint32_t ip = ((const struct in_addr *)addr)->s_addr;
    if (ip == htonl(INADDR_LOOPBACK)) {
        const char *hn = getenv("SHADOW_TPU_HOSTNAME");
        snprintf(rname, sizeof(rname), "%s", hn ? hn : "localhost");
    } else if (hosts_reverse(ip, rname, sizeof(rname)) != 0) {
        h_errno = HOST_NOT_FOUND;
        return NULL;
    }
    ra.s_addr = ip;
    ra_list[0] = (char *)&ra;
    ra_list[1] = NULL;
    rhe.h_name = rname;
    rhe.h_aliases = ra_list + 1; /* empty list */
    rhe.h_addrtype = AF_INET;
    rhe.h_length = sizeof(struct in_addr);
    rhe.h_addr_list = ra_list;
    return &rhe;
}

/* The reentrant variants (CPython's socketmodule resolves through these,
 * not the classic entry points).  One helper fills the caller's buffer. */
static int hostent_fill(struct hostent *ret, char *buf, size_t buflen,
                        const char *name, uint32_t ip,
                        struct hostent **result) {
    size_t name_len = strlen(name) + 1;
    size_t need = name_len + sizeof(struct in_addr) + 2 * sizeof(char *) + 16;
    if (buflen < need) return ERANGE;
    char *p = buf;
    memcpy(p, name, name_len);
    char *nm = p;
    p += name_len;
    p = (char *)(((uintptr_t)p + 7) & ~7ull); /* align */
    struct in_addr *a = (struct in_addr *)p;
    a->s_addr = ip;
    p += sizeof(struct in_addr);
    p = (char *)(((uintptr_t)p + 7) & ~7ull);
    char **list = (char **)p;
    list[0] = (char *)a;
    list[1] = NULL;
    ret->h_name = nm;
    ret->h_aliases = list + 1;
    ret->h_addrtype = AF_INET;
    ret->h_length = sizeof(struct in_addr);
    ret->h_addr_list = list;
    *result = ret;
    return 0;
}

int gethostbyaddr_r(const void *addr, socklen_t len, int type,
                    struct hostent *ret, char *buf, size_t buflen,
                    struct hostent **result, int *h_errnop) {
    static int (*real_r)(const void *, socklen_t, int, struct hostent *,
                         char *, size_t, struct hostent **, int *);
    if (!real_r) *(void **)&real_r = dlsym(RTLD_NEXT, "gethostbyaddr_r");
    if (!g_ready) return real_r(addr, len, type, ret, buf, buflen, result,
                                h_errnop);
    *result = NULL;
    if (type != AF_INET || len < sizeof(struct in_addr) || !addr) {
        if (h_errnop) *h_errnop = HOST_NOT_FOUND;
        return ENOENT;
    }
    uint32_t ip = ((const struct in_addr *)addr)->s_addr;
    char rname[256];
    if (ip == htonl(INADDR_LOOPBACK)) {
        const char *hn = getenv("SHADOW_TPU_HOSTNAME");
        snprintf(rname, sizeof(rname), "%s", hn ? hn : "localhost");
    } else if (hosts_reverse(ip, rname, sizeof(rname)) != 0) {
        if (h_errnop) *h_errnop = HOST_NOT_FOUND;
        return ENOENT;
    }
    return hostent_fill(ret, buf, buflen, rname, ip, result);
}

int gethostbyname_r(const char *name, struct hostent *ret, char *buf,
                    size_t buflen, struct hostent **result, int *h_errnop) {
    static int (*real_r)(const char *, struct hostent *, char *, size_t,
                         struct hostent **, int *);
    if (!real_r) *(void **)&real_r = dlsym(RTLD_NEXT, "gethostbyname_r");
    if (!g_ready) return real_r(name, ret, buf, buflen, result, h_errnop);
    *result = NULL;
    uint32_t ip;
    struct in_addr a;
    if (inet_pton(AF_INET, name, &a) == 1) {
        ip = a.s_addr;
    } else if (hosts_lookup(name, &ip) != 0) {
        if (h_errnop) *h_errnop = HOST_NOT_FOUND;
        return ENOENT;
    }
    return hostent_fill(ret, buf, buflen, name, ip, result);
}

/* Interface enumeration: apps must see the SIMULATED interfaces (lo +
 * eth0 with the host's simulated IP), not the real machine's — the
 * reference answers these via its netlink socket emulation
 * (descriptor/socket/netlink.rs) and getifaddrs preload
 * (preload-libc ifaddrs wrappers). */
#include <ifaddrs.h>
#include <net/if.h>

typedef struct {
    struct ifaddrs ifa[2];
    struct sockaddr_in addrs[6]; /* (addr, netmask, broadcast) x 2 */
    char names[2][8];
} shim_ifaddrs_blob;

static void fill_sin(struct sockaddr_in *sin, uint32_t ip_be) {
    memset(sin, 0, sizeof(*sin));
    sin->sin_family = AF_INET;
    sin->sin_addr.s_addr = ip_be;
}

int getifaddrs(struct ifaddrs **ifap) {
    static int (*real_gifa)(struct ifaddrs **);
    if (!real_gifa) *(void **)&real_gifa = dlsym(RTLD_NEXT, "getifaddrs");
    if (!g_ready) return real_gifa(ifap);
    uint32_t ip = 0;
    const char *hn = getenv("SHADOW_TPU_HOSTNAME");
    int have_ip = hn && hosts_lookup(hn, &ip) == 0;
    shim_ifaddrs_blob *b = calloc(1, sizeof(*b));
    if (!b) {
        errno = ENOMEM;
        return -1;
    }
    uint32_t mask = htonl(0xFF000000u); /* /8, the 11.0.0.0/8 assignment */
    strcpy(b->names[0], "lo");
    b->ifa[0].ifa_name = b->names[0];
    b->ifa[0].ifa_flags = IFF_UP | IFF_RUNNING | IFF_LOOPBACK;
    fill_sin(&b->addrs[0], htonl(INADDR_LOOPBACK));
    fill_sin(&b->addrs[1], mask);
    b->ifa[0].ifa_addr = (struct sockaddr *)&b->addrs[0];
    b->ifa[0].ifa_netmask = (struct sockaddr *)&b->addrs[1];
    if (have_ip) {
        b->ifa[0].ifa_next = &b->ifa[1];
        strcpy(b->names[1], "eth0");
        b->ifa[1].ifa_name = b->names[1];
        b->ifa[1].ifa_flags =
            IFF_UP | IFF_RUNNING | IFF_BROADCAST | IFF_MULTICAST;
        fill_sin(&b->addrs[2], ip);
        fill_sin(&b->addrs[3], mask);
        fill_sin(&b->addrs[4], ip | ~mask);
        b->ifa[1].ifa_addr = (struct sockaddr *)&b->addrs[2];
        b->ifa[1].ifa_netmask = (struct sockaddr *)&b->addrs[3];
        b->ifa[1].ifa_broadaddr = (struct sockaddr *)&b->addrs[4];
    }
    *ifap = &b->ifa[0];
    return 0;
}

void freeifaddrs(struct ifaddrs *ifa) {
    static void (*real_fifa)(struct ifaddrs *);
    if (!real_fifa) *(void **)&real_fifa = dlsym(RTLD_NEXT, "freeifaddrs");
    if (!g_ready) {
        real_fifa(ifa);
        return;
    }
    free(ifa); /* the blob starts at ifa[0] */
}

unsigned int if_nametoindex(const char *name) {
    static unsigned int (*real_nti)(const char *);
    if (!real_nti) *(void **)&real_nti = dlsym(RTLD_NEXT, "if_nametoindex");
    if (!g_ready) return real_nti(name);
    if (strcmp(name, "lo") == 0) return 1;
    if (strcmp(name, "eth0") == 0) return 2;
    errno = ENODEV;
    return 0;
}

char *if_indextoname(unsigned int ifindex, char ifname[IF_NAMESIZE]) {
    static char *(*real_itn)(unsigned int, char *);
    if (!real_itn) *(void **)&real_itn = dlsym(RTLD_NEXT, "if_indextoname");
    if (!g_ready) return real_itn(ifindex, ifname);
    if (ifindex == 1) return strcpy(ifname, "lo");
    if (ifindex == 2) return strcpy(ifname, "eth0");
    errno = ENXIO;
    return NULL;
}

/* the local hostname is the simulated one */
int gethostname(char *name, size_t len) {
    if (!real_socket) resolve_reals();
    static int (*real_ghname)(char *, size_t);
    if (!real_ghname) real_ghname = dlsym(RTLD_NEXT, "gethostname");
    const char *simname = getenv("SHADOW_TPU_HOSTNAME");
    if (!g_ready || !simname) return real_ghname(name, len);
    snprintf(name, len, "%s", simname);
    return 0;
}


/* ------------------------------------------------------------- threads */

/* pthread support: each new thread gets its own futex channel via the
 * PRETHREAD / THREAD_CREATED / THREAD_START handshake (the thread analog
 * of the fork handshake below, mirroring the reference's per-thread
 * IPCData + native_clone flow, managed_thread.rs:355).  The manager
 * schedules thread turns like process turns, so a thread only runs while
 * the simulation has handed it the turn.
 *
 * Mutexes, condvars, and unnamed semaphores are virtualized MANAGER-SIDE,
 * keyed by object address (the futex-table analog, host/futex_table.rs):
 * a native lock would block the OS thread outside the simulation and
 * deadlock the turn.  Well-synchronized plugins stay deterministic;
 * plugins with genuine data races were racy on real Linux too. */

#define SHIM_MAX_THREADS 512
static struct {
    pthread_t th;
    int64_t vtid;
    int used;
} thread_tab[SHIM_MAX_THREADS];

static void shim_thread_table_reset(void) {
    memset(thread_tab, 0, sizeof(thread_tab));
}

static int64_t thread_vtid_of(pthread_t th) {
    for (int i = 0; i < SHIM_MAX_THREADS; i++)
        if (thread_tab[i].used && pthread_equal(thread_tab[i].th, th))
            return thread_tab[i].vtid;
    return 0;
}

static void thread_table_remove(pthread_t th) {
    for (int i = 0; i < SHIM_MAX_THREADS; i++)
        if (thread_tab[i].used && pthread_equal(thread_tab[i].th, th))
            thread_tab[i].used = 0;
}

/* fire-and-forget farewell on the exiting thread's own channel (the
 * manager is blocked on it); no reply — the OS thread is on its way out */
static void thread_send_exit(void *retval) {
    if (t_exit_sent) return;
    t_exit_sent = 1;
    shim_msg *tx = &cur_shm()->to_shadow;
    tx->op = SHIM_OP_THREAD_EXIT;
    tx->args[0] = t_vtid;
    tx->args[1] = (int64_t)(uintptr_t)retval;
    for (int i = 2; i < 6; i++) tx->args[i] = 0;
    tx->payload_len = 0;
    msg_publish(tx);
}

/* shared manager-handshake steps of pthread_create AND raw-clone
 * adoption: reserve a channel (PRETHREAD), confirm/cancel it
 * (THREAD_CREATED), and register the backing pthread for joins */
static int64_t shim_prethread(char *path, uint32_t pathsz, int64_t *vtid) {
    uint32_t len = pathsz - 1;
    int64_t reply[6];
    int64_t ret = shim_call(SHIM_OP_PRETHREAD, NULL, NULL, 0, path, &len,
                            reply);
    if (ret < 0) return ret;
    path[len] = 0;
    *vtid = reply[1];
    return 0;
}

static void shim_thread_created(int64_t vtid, int failed) {
    int64_t args[6] = {vtid, failed, 0, 0, 0, 0};
    shim_call(SHIM_OP_THREAD_CREATED, args, NULL, 0, NULL, NULL, NULL);
}

static void thread_tab_register(pthread_t th, int64_t vtid) {
    for (int i = 0; i < SHIM_MAX_THREADS; i++) {
        if (!thread_tab[i].used) {
            thread_tab[i].th = th;
            thread_tab[i].vtid = vtid;
            thread_tab[i].used = 1;
            break;
        }
    }
}

typedef struct {
    void *(*start)(void *);
    void *arg;
    shim_shmem *shm;
    int64_t vtid;
} shim_thread_boot;

static void *shim_thread_tramp(void *p) {
    /* dispatch is per-thread: arm before anything else (we are in shim
     * text, so nothing here can escape beforehand) */
    if (g_sud_on) sud_arm();
    if (g_tsc_on) tsc_arm();
    shim_thread_boot boot = *(shim_thread_boot *)p;
    t_shm = boot.shm;
    t_vtid = boot.vtid;
    /* parks here until the thread's start event fires in the simulation */
    int64_t args[6] = {boot.vtid, 0, 0, 0, 0, 0};
    shim_call(SHIM_OP_THREAD_START, args, NULL, 0, NULL, NULL, NULL);
    /* only now: until its first turn this thread runs BESIDE its creator
     * (the manager has answered THREAD_CREATED), and free() contending
     * with the creator's next malloc is a raw futex from libc text — armed
     * above, it was emulated on cur_shm() = the MAIN thread's channel,
     * t_shm not being set yet: two threads on one channel, seen under CPU
     * load as a plugin that stops answering or a garbled PRETHREAD path */
    free(p);
    void *ret = boot.start(boot.arg);
    thread_send_exit(ret);
    return ret;
}

int pthread_create(pthread_t *th, const pthread_attr_t *attr,
                   void *(*start)(void *), void *arg) {
    static int (*real_create)(pthread_t *, const pthread_attr_t *,
                              void *(*)(void *), void *);
    if (!real_create) *(void **)&real_create = dlsym(RTLD_NEXT, "pthread_create");
    if (!g_ready) return real_create(th, attr, start, arg);
    char path[480];
    int64_t vtid;
    int64_t ret = shim_prethread(path, sizeof(path), &vtid);
    if (ret < 0) return (int)-ret;
    shim_thread_boot *boot = malloc(sizeof(*boot));
    if (!boot) {
        /* cancel so the manager frees the pending channel + file */
        shim_thread_created(vtid, 1);
        return ENOMEM;
    }
    boot->start = start;
    boot->arg = arg;
    boot->shm = shim_map(path);
    boot->vtid = vtid;
    /* glibc's pthread_create issues a CLONE_VM clone from libc text; that
     * cannot be re-executed from the SIGSYS handler (the child would
     * resume mid-handler on the new thread's stack).  Lift dispatch for
     * the duration: no other simulation thread runs concurrently (strict
     * turn-taking), and the new thread re-arms itself first thing in the
     * trampoline. */
    if (g_sud_on) g_sud_selector = SYSCALL_DISPATCH_FILTER_ALLOW;
    int r = real_create(th, attr, shim_thread_tramp, boot);
    if (g_sud_on) g_sud_selector = SYSCALL_DISPATCH_FILTER_BLOCK;
    shim_thread_created(vtid, r != 0);
    if (r != 0) {
        munmap(boot->shm, sizeof(shim_shmem));
        free(boot);
        return r;
    }
    thread_tab_register(*th, vtid);
    return 0;
}

/* ---- raw CLONE_VM thread adoption (the Go runtime's newosproc path) ----
 *
 * Language runtimes that do not use libc threads create OS threads with a
 * raw clone(CLONE_VM|CLONE_THREAD|...) from their own text, expecting the
 * kernel contract: the child resumes at the instruction after the syscall
 * with rax = 0 on the caller-provided stack.  Re-executing that clone from
 * the SIGSYS handler is unsound (the child would resume inside the
 * handler frame on a foreign stack), and a directly-cloned child would
 * share the parent's glibc TLS (no CLONE_SETTLS in Go's flag set), so the
 * shim's own __thread state would be corrupted.
 *
 * Adoption instead backs the app's thread with a REAL pthread: the new
 * OS thread gets proper glibc TLS (shim state keeps working forever), is
 * registered with the manager through the ordinary PRETHREAD /
 * THREAD_CREATED / THREAD_START handshake (so it takes simulation turns
 * like any managed thread), and then a register-restore trampoline
 * reproduces the kernel contract exactly: every GPR from the interrupted
 * context, rflags, rax = 0, rsp = the app's child stack, jump to the
 * post-syscall ip.  rcx/r11 are syscall-clobbered by the ABI, so they
 * are free as scratch.  CLONE_PARENT_SETTID / CHILD_SETTID are emulated
 * with the real OS tid; CHILD_CLEARTID clears and futex-wakes (through
 * the EMULATED futex, where the joiner waits) at thread exit.
 * CLONE_SETTLS is refused — a runtime that manages libc-level TLS itself
 * must come through pthread_create.  (The reference runs Go through its
 * own native_clone flow, managed_thread.rs:355; this is the shim-side
 * equivalent.) */

typedef struct {
    shim_shmem *shm;
    int64_t vtid;
    unsigned long fl;
    int *ctid;
    volatile int tid; /* commbox: child publishes its OS tid */
    int has_fp;
    /* retirement: raw SYS_exit siglongjmps back into the trampoline's
     * frame on the (untouched) pthread stack, so the trampoline RETURNS
     * and glibc reclaims the detached backing thread normally — no
     * unwinding through signal frames, no stack/TCB leak */
    sigjmp_buf retire;
    void *exit_val;
    long long gregs[23];
    /* the interrupted context's FPU/SSE environment (MXCSR, x87 control
     * word, register file): the kernel clone contract copies it into the
     * child, so the restore must too */
    char fpstate[512] __attribute__((aligned(16)));
} adopt_boot;

__attribute__((noreturn, used)) void shim_adopted_jump(const long long *g,
                                                       const void *fp);
__asm__(
    ".text\n"
    ".type shim_adopted_jump, @function\n"
    "shim_adopted_jump:\n"
    "  test %rsi, %rsi\n"
    "  jz 2f\n"
    "  fxrstor64 (%rsi)\n"
    "2:\n"
    "  mov %rdi, %r11\n"
    /* glibc mcontext greg order: r8 r9 r10 r11 r12 r13 r14 r15 rdi rsi
     * rbp rbx rdx rax rcx rsp rip efl ... (8 bytes each) */
    "  mov 0(%r11), %r8\n"
    "  mov 8(%r11), %r9\n"
    "  mov 16(%r11), %r10\n"
    "  mov 32(%r11), %r12\n"
    "  mov 40(%r11), %r13\n"
    "  mov 48(%r11), %r14\n"
    "  mov 56(%r11), %r15\n"
    "  mov 72(%r11), %rsi\n"
    "  mov 80(%r11), %rbp\n"
    "  mov 88(%r11), %rbx\n"
    "  mov 96(%r11), %rdx\n"
    "  mov 120(%r11), %rsp\n"   /* the app's child stack */
    "  pushq 128(%r11)\n"       /* post-syscall rip */
    "  pushq 136(%r11)\n"       /* rflags */
    "  mov 64(%r11), %rdi\n"
    "  mov 112(%r11), %rcx\n"
    "  xor %eax, %eax\n"        /* clone returns 0 in the child */
    "  popfq\n"
    "  ret\n"
    ".size shim_adopted_jump, .-shim_adopted_jump\n");

static long shim_futex_emu(long uaddr, long op, long val, long timeout,
                           long uaddr2, long val3);

static void *shim_adopted_tramp(void *p) {
    /* copy the boot block into THIS frame: the dying thread must not
     * take malloc locks after the farewell (another sim thread's
     * contended malloc futex is EMULATED; a raw unlock would never wake
     * it), so the PARENT owns and frees the heap block — publishing the
     * tid through it is this thread's last touch of it */
    adopt_boot boot = *(adopt_boot *)p;
    if (g_sud_on) sud_arm();
    if (g_tsc_on) tsc_arm();
    t_shm = boot.shm;
    t_vtid = boot.vtid;
    t_boot = &boot;
    int tid = (int)shim_raw_syscall6(SYS_gettid, 0, 0, 0, 0, 0, 0);
    if ((boot.fl & CLONE_CHILD_SETTID) && boot.ctid) *boot.ctid = tid;
    ((adopt_boot *)p)->tid = tid;
    shim_raw_syscall6(SYS_futex, (long)&((adopt_boot *)p)->tid,
                      FUTEX_WAKE, 1, 0, 0, 0);
    p = NULL; /* parent frees it the moment it reads the tid */
    /* parks here until the thread's start event fires in the simulation */
    int64_t args[6] = {boot.vtid, 0, 0, 0, 0, 0};
    shim_call(SHIM_OP_THREAD_START, args, NULL, 0, NULL, NULL, NULL);
    if (sigsetjmp(boot.retire, 0) == 0)
        shim_adopted_jump(boot.gregs,
                          boot.has_fp ? boot.fpstate : NULL);
    /* Raw SYS_exit longjmp'd back: we are on the PTHREAD stack now and
     * will never touch the app's clone stack again — only NOW may the
     * joiner learn the thread is gone.  Kernel ctid law: clear + wake
     * (through the EMULATED futex, where the joiner waits — the channel
     * is still live, the farewell comes after), then retire.  The
     * trampoline returns so glibc reclaims the detached backing thread
     * (stack, TCB) through its normal path.  Residual narrow race,
     * documented: glibc's thread-teardown freeres may take a malloc
     * arena lock with raw futexes after the farewell; an app thread
     * sharing that arena contends through the emulated futex.  The
     * churn stress (520 lifetimes) exercises this path. */
    if ((boot.fl & CLONE_CHILD_CLEARTID) && boot.ctid) {
        *boot.ctid = 0;
        shim_futex_emu((long)boot.ctid, FUTEX_WAKE, 0x7FFFFFFF, 0, 0, 0);
    }
    thread_send_exit(boot.exit_val);
    if (g_sud_on)
        shim_raw_syscall6(SYS_prctl, PR_SET_SYSCALL_USER_DISPATCH,
                          PR_SYS_DISPATCH_OFF, 0, 0, 0, 0);
    return boot.exit_val;
}

/* One adoption in flight at most — turn-taking parks every other sim
 * thread while the SIGSYS handler runs, and the parent side waits for the
 * child's tid publish (its LAST touch of the block) before returning —
 * so a single static boot block replaces malloc: the handler may run
 * inside a runtime's own allocation path (musl internals issue raw
 * clone), where taking the malloc lock would self-deadlock. */
static adopt_boot g_adopt_boot;

static long shim_adopt_raw_thread(ucontext_t *uc, unsigned long fl,
                                  long stack, long ptid, long ctid) {
    if (!stack) return -EINVAL;
    char path[480];
    int64_t vtid;
    int64_t ret = shim_prethread(path, sizeof(path), &vtid);
    if (ret < 0) return ret;
    adopt_boot *boot = &g_adopt_boot;
    shim_shmem *shm = shim_map(path);
    if (!shm) {
        /* cancel so the manager frees the pending channel + file */
        shim_thread_created(vtid, 1);
        return -ENOMEM;
    }
    boot->shm = shm;
    boot->vtid = vtid;
    boot->fl = fl;
    boot->ctid = (int *)ctid;
    boot->tid = 0;
    memcpy(boot->gregs, uc->uc_mcontext.gregs, sizeof(boot->gregs));
    boot->gregs[REG_RSP] = stack;
    boot->has_fp = uc->uc_mcontext.fpregs != NULL;
    if (boot->has_fp)
        memcpy(boot->fpstate, uc->uc_mcontext.fpregs,
               sizeof(boot->fpstate));
    /* g_real_pthread_create is pre-resolved in shim_init: dlsym from a
     * signal handler could itself allocate */
    int (*real_create)(pthread_t *, const pthread_attr_t *,
                       void *(*)(void *), void *) = g_real_pthread_create;
    if (!real_create) {
        shim_thread_created(vtid, 1);
        munmap(shm, sizeof(shim_shmem));
        return -ENOSYS;
    }
    pthread_attr_t attr;
    pthread_attr_init(&attr);
    pthread_attr_setdetachstate(&attr, PTHREAD_CREATE_DETACHED);
    /* the pthread stack only hosts the trampoline and signal frames —
     * after the jump the thread lives on the app's stack */
    pthread_attr_setstacksize(&attr, 256 * 1024);
    pthread_t th;
    /* the libc-internal clone comes from libc text: lift dispatch for
     * the duration (turn-taking means no other sim thread runs) */
    if (g_sud_on) g_sud_selector = SYSCALL_DISPATCH_FILTER_ALLOW;
    int r = real_create(&th, &attr, shim_adopted_tramp, boot);
    if (g_sud_on) g_sud_selector = SYSCALL_DISPATCH_FILTER_BLOCK;
    pthread_attr_destroy(&attr);
    shim_thread_created(vtid, r != 0);
    if (r != 0) {
        munmap(shm, sizeof(shim_shmem));
        return -EAGAIN;
    }
    /* the tid handshake costs microseconds of wall time, never sim time;
     * the child's tid publish is its LAST touch of the static boot block,
     * so the block is free for the next adoption once this returns */
    while (!boot->tid)
        shim_raw_syscall6(SYS_futex, (long)&boot->tid, FUTEX_WAIT, 0, 0, 0,
                          0);
    int tid = boot->tid;
    if ((fl & CLONE_PARENT_SETTID) && ptid) *(int *)ptid = tid;
    thread_tab_register(th, vtid);
    return tid;
}

int pthread_join(pthread_t th, void **retval) {
    static int (*real_join)(pthread_t, void **);
    if (!real_join) *(void **)&real_join = dlsym(RTLD_NEXT, "pthread_join");
    if (!g_ready) return real_join(th, retval);
    int64_t vtid = thread_vtid_of(th);
    if (!vtid) return real_join(th, retval); /* created pre-init: native */
    int64_t args[6] = {vtid, 0, 0, 0, 0, 0};
    int64_t reply[6];
    int64_t ret = shim_call(SHIM_OP_THREAD_JOIN, args, NULL, 0, NULL, NULL, reply);
    if (ret < 0) return (int)-ret; /* pthread API returns the error code */
    if (retval) *retval = (void *)(uintptr_t)reply[1];
    thread_table_remove(th);
    /* reap the OS thread: it exits right after its farewell, so this
     * blocks microseconds of wall time, never simulated time */
    return real_join(th, NULL);
}

int pthread_detach(pthread_t th) {
    static int (*real_detach)(pthread_t);
    if (!real_detach) *(void **)&real_detach = dlsym(RTLD_NEXT, "pthread_detach");
    if (!g_ready) return real_detach(th);
    int64_t vtid = thread_vtid_of(th);
    if (vtid) {
        int64_t args[6] = {vtid, 1, 0, 0, 0, 0};
        shim_call(SHIM_OP_THREAD_JOIN, args, NULL, 0, NULL, NULL, NULL);
        thread_table_remove(th);
    }
    return real_detach(th);
}

void pthread_exit(void *retval) {
    static void (*real_pexit)(void *) __attribute__((noreturn));
    if (!real_pexit) *(void **)&real_pexit = dlsym(RTLD_NEXT, "pthread_exit");
    /* vtid 0 = the MAIN thread retiring while others run: the manager
     * stops servicing its channel and waits for the process farewell */
    if (g_ready) thread_send_exit(retval);
    real_pexit(retval);
    __builtin_unreachable();
}

/* -- virtualized sync primitives -------------------------------------- */

static int sync_call2(uint32_t op, int64_t a0, int64_t a1, int64_t a2,
                      int64_t reply[6]) {
    int64_t args[6] = {a0, a1, a2, 0, 0, 0};
    int64_t ret = shim_call(op, args, NULL, 0, NULL, NULL, reply);
    return ret < 0 ? (int)-ret : 0;
}

/* absolute sim-clock timespec -> relative ns (floor 0); -1 if null */
static int64_t abs_to_rel_ns(const struct timespec *abstime) {
    if (!abstime) return -1;
    int64_t abs_ns =
        (int64_t)abstime->tv_sec * 1000000000ll + abstime->tv_nsec;
    int64_t now = (int64_t)sim_now_ns();
    return abs_ns > now ? abs_ns - now : 0;
}

int pthread_mutex_lock(pthread_mutex_t *m) {
    static int (*real_lock)(pthread_mutex_t *);
    if (!real_lock) *(void **)&real_lock = dlsym(RTLD_NEXT, "pthread_mutex_lock");
    if (!g_ready) return real_lock(m);
    return sync_call2(SHIM_OP_MUTEX_LOCK, (int64_t)(uintptr_t)m, 0, -1, NULL);
}

int pthread_mutex_trylock(pthread_mutex_t *m) {
    static int (*real_try)(pthread_mutex_t *);
    if (!real_try) *(void **)&real_try = dlsym(RTLD_NEXT, "pthread_mutex_trylock");
    if (!g_ready) return real_try(m);
    return sync_call2(SHIM_OP_MUTEX_LOCK, (int64_t)(uintptr_t)m, 1, -1, NULL);
}

int pthread_mutex_timedlock(pthread_mutex_t *m, const struct timespec *abstime) {
    static int (*real_timed)(pthread_mutex_t *, const struct timespec *);
    if (!real_timed) *(void **)&real_timed = dlsym(RTLD_NEXT, "pthread_mutex_timedlock");
    if (!g_ready) return real_timed(m, abstime);
    return sync_call2(SHIM_OP_MUTEX_LOCK, (int64_t)(uintptr_t)m, 0,
                      abs_to_rel_ns(abstime), NULL);
}

int pthread_mutex_unlock(pthread_mutex_t *m) {
    static int (*real_unlock)(pthread_mutex_t *);
    if (!real_unlock) *(void **)&real_unlock = dlsym(RTLD_NEXT, "pthread_mutex_unlock");
    if (!g_ready) return real_unlock(m);
    return sync_call2(SHIM_OP_MUTEX_UNLOCK, (int64_t)(uintptr_t)m, 0, 0, NULL);
}

int pthread_cond_wait(pthread_cond_t *c, pthread_mutex_t *m) {
    static int (*real_wait)(pthread_cond_t *, pthread_mutex_t *);
    if (!real_wait) *(void **)&real_wait = dlsym(RTLD_NEXT, "pthread_cond_wait");
    if (!g_ready) return real_wait(c, m);
    return sync_call2(SHIM_OP_COND_WAIT, (int64_t)(uintptr_t)c,
                      (int64_t)(uintptr_t)m, -1, NULL);
}

int pthread_cond_timedwait(pthread_cond_t *c, pthread_mutex_t *m,
                           const struct timespec *abstime) {
    static int (*real_twait)(pthread_cond_t *, pthread_mutex_t *,
                             const struct timespec *);
    if (!real_twait) *(void **)&real_twait = dlsym(RTLD_NEXT, "pthread_cond_timedwait");
    if (!g_ready) return real_twait(c, m, abstime);
    return sync_call2(SHIM_OP_COND_WAIT, (int64_t)(uintptr_t)c,
                      (int64_t)(uintptr_t)m, abs_to_rel_ns(abstime), NULL);
}

int pthread_cond_signal(pthread_cond_t *c) {
    static int (*real_sig)(pthread_cond_t *);
    if (!real_sig) *(void **)&real_sig = dlsym(RTLD_NEXT, "pthread_cond_signal");
    if (!g_ready) return real_sig(c);
    return sync_call2(SHIM_OP_COND_WAKE, (int64_t)(uintptr_t)c, 0, 0, NULL);
}

int pthread_cond_broadcast(pthread_cond_t *c) {
    static int (*real_bcast)(pthread_cond_t *);
    if (!real_bcast) *(void **)&real_bcast = dlsym(RTLD_NEXT, "pthread_cond_broadcast");
    if (!g_ready) return real_bcast(c);
    return sync_call2(SHIM_OP_COND_WAKE, (int64_t)(uintptr_t)c, 1, 0, NULL);
}

/* unnamed semaphores (sem_open named ones stay native) */
int sem_init(sem_t *s, int pshared, unsigned int value) {
    static int (*real_init)(sem_t *, int, unsigned int);
    if (!real_init) *(void **)&real_init = dlsym(RTLD_NEXT, "sem_init");
    if (!g_ready) return real_init(s, pshared, value);
    (void)pshared; /* threads of one process only */
    int e = sync_call2(SHIM_OP_SEM_INIT, (int64_t)(uintptr_t)s, value, 0, NULL);
    if (e) {
        errno = e;
        return -1;
    }
    return 0;
}

static int sem_wait_common(sem_t *s, int try_, int64_t timeout_ns) {
    int64_t e = sync_call2(SHIM_OP_SEM_WAIT, (int64_t)(uintptr_t)s, try_,
                           timeout_ns, NULL);
    if (e) {
        errno = (int)e;
        return -1;
    }
    return 0;
}

int sem_wait(sem_t *s) {
    static int (*real_wait)(sem_t *);
    if (!real_wait) *(void **)&real_wait = dlsym(RTLD_NEXT, "sem_wait");
    if (!g_ready) return real_wait(s);
    return sem_wait_common(s, 0, -1);
}

int sem_trywait(sem_t *s) {
    static int (*real_try)(sem_t *);
    if (!real_try) *(void **)&real_try = dlsym(RTLD_NEXT, "sem_trywait");
    if (!g_ready) return real_try(s);
    return sem_wait_common(s, 1, -1);
}

int sem_timedwait(sem_t *s, const struct timespec *abstime) {
    static int (*real_timed)(sem_t *, const struct timespec *);
    if (!real_timed) *(void **)&real_timed = dlsym(RTLD_NEXT, "sem_timedwait");
    if (!g_ready) return real_timed(s, abstime);
    return sem_wait_common(s, 0, abs_to_rel_ns(abstime));
}

int sem_post(sem_t *s) {
    static int (*real_post)(sem_t *);
    if (!real_post) *(void **)&real_post = dlsym(RTLD_NEXT, "sem_post");
    if (!g_ready) return real_post(s);
    int e = sync_call2(SHIM_OP_SEM_POST, (int64_t)(uintptr_t)s, 0, 0, NULL);
    if (e) {
        errno = e;
        return -1;
    }
    return 0;
}

int sem_getvalue(sem_t *s, int *sval) {
    static int (*real_get)(sem_t *, int *);
    if (!real_get) *(void **)&real_get = dlsym(RTLD_NEXT, "sem_getvalue");
    if (!g_ready) return real_get(s, sval);
    int64_t reply[6];
    int e = sync_call2(SHIM_OP_SEM_GET, (int64_t)(uintptr_t)s, 0, 0, reply);
    if (e) {
        errno = e;
        return -1;
    }
    *sval = (int)reply[1];
    return 0;
}

/* ---------------------------------------------------------- fork / wait */

void exit(int status) {
    static void (*real_exit)(int) __attribute__((noreturn));
    if (!real_exit) *(void **)&real_exit = dlsym(RTLD_NEXT, "exit");
    g_exit_code = status;
    real_exit(status);
    __builtin_unreachable();
}

/* Fork under the simulator: the parent asks the manager to prepare a
 * fresh channel, the child attaches it and parks until the simulation
 * hands it the turn — both processes only ever run while scheduled, the
 * turn-taking the reference enforces per managed thread
 * (managed_thread.rs native_clone).  The child env points at its own
 * channel so an exec'd program's fresh shim re-registers on it. */
/* -- simulated signals (handler/signal.rs, shim/src/signals.rs) --------- */
/* kill between simulated processes routes through the manager: the signal
 * lands at a simulated instant and only at a turn boundary (the target is
 * parked or mid-exchange; shim_call masks deliverable signals during
 * exchanges, so handlers run BETWEEN interposed calls).  The manager
 * refuses pids it does not manage — a plugin cannot signal the real OS. */
int kill(pid_t pid, int sig) {
    static int (*real_kill)(pid_t, int);
    if (!real_kill) *(void **)&real_kill = dlsym(RTLD_NEXT, "kill");
    if (!g_ready) return real_kill(pid, sig);
    if (pid == 0 || pid == -1) {
        /* own process group / everyone: under the simulation that is this
         * app's process tree — the manager fans the delivery out */
        pid = 0;
    } else if (pid < 0) {
        pid = -pid; /* a specific group id == its leader's pid here */
    }
    int64_t args[6] = {pid, sig, 0, 0, 0, 0};
    return (int)ret_errno(
        shim_call(SHIM_OP_KILL, args, NULL, 0, NULL, NULL, NULL));
}

/* alarm/setitimer(ITIMER_REAL) tick the SIMULATED clock: the manager
 * schedules the expiry and delivers SIGALRM at that simulated instant. */
static int64_t alarm_set_ns(int64_t ns, int64_t interval_ns) {
    int64_t args[6] = {ns, interval_ns, 0, 0, 0, 0};
    int64_t reply[6];
    int64_t ret =
        shim_call(SHIM_OP_ALARM, args, NULL, 0, NULL, NULL, reply);
    return ret < 0 ? 0 : reply[1];
}

unsigned int alarm(unsigned int seconds) {
    static unsigned int (*real_alarm)(unsigned int);
    if (!real_alarm) *(void **)&real_alarm = dlsym(RTLD_NEXT, "alarm");
    if (!g_ready) return real_alarm(seconds);
    int64_t old = alarm_set_ns((int64_t)seconds * 1000000000ll, 0);
    return (unsigned int)((old + 999999999ll) / 1000000000ll);
}

int setitimer(__itimer_which_t which, const struct itimerval *new_value,
              struct itimerval *old_value) {
    static int (*real_seti)(__itimer_which_t, const struct itimerval *,
                            struct itimerval *);
    if (!real_seti) *(void **)&real_seti = dlsym(RTLD_NEXT, "setitimer");
    if (!g_ready) return real_seti(which, new_value, old_value);
    if (which != ITIMER_REAL) {
        /* the shim itself owns ITIMER_VIRTUAL for CPU-time preemption —
         * an app timer would clobber the quantum AND deliver a real
         * SIGVTALRM/SIGPROF outside simulated causality.  Refuse loudly
         * (ENOTSUP) rather than silently breaking determinism. */
        static int warned;
        if (!warned++)
            shim_warn("setitimer(ITIMER_VIRTUAL/PROF) is not simulated; "
                      "refusing with ENOTSUP");
        errno = ENOTSUP;
        return -1;
    }
    if (!new_value) {
        errno = EFAULT;
        return -1;
    }
    int64_t ns = (int64_t)new_value->it_value.tv_sec * 1000000000ll +
                 (int64_t)new_value->it_value.tv_usec * 1000ll;
    int64_t ins = (int64_t)new_value->it_interval.tv_sec * 1000000000ll +
                  (int64_t)new_value->it_interval.tv_usec * 1000ll;
    int64_t old = alarm_set_ns(ns, ins);
    if (old_value) {
        memset(old_value, 0, sizeof(*old_value));
        old_value->it_value.tv_sec = old / 1000000000ll;
        old_value->it_value.tv_usec = (old % 1000000000ll) / 1000;
    }
    return 0;
}

/* Inside glibc's fork the raw clone comes from libc text and traps; the
 * dispatcher must re-execute it raw (re-arming dispatch on the child
 * side) instead of recursing into this wrapper.  Thread-local flag
 * distinguishes that inner clone from an app's own raw fork/clone. */
static __thread int t_in_fork;

pid_t fork(void) {
    static pid_t (*real_fork)(void);
    if (!real_fork) *(void **)&real_fork = dlsym(RTLD_NEXT, "fork");
    if (!g_ready) return real_fork();
    char path[480];
    uint32_t len = sizeof(path) - 1;
    int64_t ret =
        shim_call(SHIM_OP_PREFORK, NULL, NULL, 0, path, &len, NULL);
    if (ret < 0) {
        errno = (int)-ret;
        return -1;
    }
    path[len] = 0;
    t_in_fork = 1;
    pid_t pid = real_fork();
    t_in_fork = 0;
    if (pid < 0) return pid;
    if (pid == 0) {
        /* dispatch is per-thread state the child did not inherit; re-arm
         * before any app code runs (under legacy seccomp the filter IS
         * inherited and nothing is needed).  The CPU-time itimer is also
         * cleared by fork. */
        if (g_sud_on) sud_arm();
        if (g_tsc_on) tsc_arm();
        preempt_arm();
        setenv("SHADOW_TPU_SHM", path, 1);
        /* only the calling thread exists in the child (POSIX): it becomes
         * the main thread of a fresh single-threaded process */
        t_shm = NULL;
        t_vtid = 0;
        t_exit_sent = 0;
        shim_thread_table_reset();
        shim_attach(path);
        int64_t args[6] = {getpid(), 0, 0, 0, 0, 0};
        /* parks here until the child's start event fires in the sim */
        shim_call(SHIM_OP_CHILD_START, args, NULL, 0, NULL, NULL, NULL);
        return 0;
    }
    int64_t args[6] = {pid, 0, 0, 0, 0, 0};
    shim_call(SHIM_OP_FORKED, args, NULL, 0, NULL, NULL, NULL);
    return pid;
}

/* vfork's share-the-address-space semantics cannot coexist with the
 * child-side channel attach; full fork semantics satisfy every correct
 * vfork user (they may only exec or _exit) */
pid_t vfork(void) { return fork(); }

/* waitpid must park in SIMULATED time: the child only runs when the sim
 * schedules it, so a native blocking waitpid would deadlock the turn. */
pid_t waitpid(pid_t pid, int *wstatus, int options) {
    static pid_t (*real_waitpid)(pid_t, int *, int);
    if (!real_waitpid) *(void **)&real_waitpid = dlsym(RTLD_NEXT, "waitpid");
    if (!g_ready) return real_waitpid(pid, wstatus, options);
    int64_t args[6] = {pid, (options & WNOHANG) ? 1 : 0, 0, 0, 0, 0};
    int64_t reply[6];
    int64_t ret = shim_call(SHIM_OP_WAITPID, args, NULL, 0, NULL, NULL, reply);
    if (ret < 0) {
        errno = (int)-ret;
        return -1;
    }
    if (ret > 0 && wstatus) *wstatus = (int)reply[1];
    return (pid_t)ret;
}

pid_t wait(int *wstatus) { return waitpid(-1, wstatus, 0); }

pid_t wait3(int *wstatus, int options, struct rusage *ru) {
    if (ru) memset(ru, 0, sizeof(*ru));
    return waitpid(-1, wstatus, options);
}

pid_t wait4(pid_t pid, int *wstatus, int options, struct rusage *ru) {
    if (ru) memset(ru, 0, sizeof(*ru));
    return waitpid(pid, wstatus, options);
}

/* Capture main()'s return value: glibc's __libc_start_main calls its
 * internal exit alias (not the PLT), so the exit() wrapper alone misses
 * `return code;` from main.  Wrapping main via __libc_start_main is the
 * standard LD_PRELOAD technique. */
static int (*g_real_main)(int, char **, char **);

static int shim_main_wrapper(int argc, char **argv, char **envp) {
    int r = g_real_main(argc, argv, envp);
    g_exit_code = r;
    return r;
}

int __libc_start_main(int (*m)(int, char **, char **), int argc, char **av,
                      void (*init)(void), void (*fini)(void),
                      void (*rtld_fini)(void), void *stack_end) {
    static int (*real_start)(int (*)(int, char **, char **), int, char **,
                             void (*)(void), void (*)(void), void (*)(void),
                             void *);
    if (!real_start)
        *(void **)&real_start = dlsym(RTLD_NEXT, "__libc_start_main");
    g_real_main = m;
    return real_start(shim_main_wrapper, argc, av, init, fini, rtld_fini,
                      stack_end);
}

/* exec: the caller may pass a hand-built envp (bash execs commands with
 * its internal export list, not libc environ), which would carry the
 * PARENT's channel path into the child program.  Rewrite the env so the
 * exec'd program's fresh shim attaches THIS process's channel. */
static int raw_execve(const char *path, char *const argv[],
                      char *const envp[]) {
    /* raw: reachable from the dispatcher (a raw SYS_execve still gets its
     * environment rewritten), and SUD resets across exec so the new image
     * starts clean.  PR_SET_TSC however SURVIVES exec while the SIGSEGV
     * handler does not — an early rdtsc in the new image's ld.so/libc
     * startup would be fatal; disarm here, the fresh shim re-arms. */
    tsc_disarm_for_exec();
    long r = shim_raw_syscall6(SYS_execve, (long)path, (long)argv,
                               (long)envp, 0, 0, 0);
    /* only reached on failure: restore the trap so TSC reads stay
     * simulated in the continuing image */
    if (g_tsc_on)
        shim_raw_syscall6(SYS_prctl, PR_SET_TSC, PR_TSC_SIGSEGV, 0, 0, 0, 0);
    return (int)raw_ret(r);
}

static int shim_execve(const char *path, char *const argv[],
                       char *const envp[]) {
    static int (*real_execve)(const char *, char *const[], char *const[]) =
        raw_execve;
    if (!g_ready) return real_execve(path, argv, envp);
    const char *shm = getenv("SHADOW_TPU_SHM");
    const char *preload = getenv("LD_PRELOAD");
    int n = 0;
    while (envp && envp[n]) n++;
    char **nenv = malloc((size_t)(n + 3) * sizeof(char *));
    if (!nenv) return real_execve(path, argv, envp);
    char shm_kv[512], pre_kv[1024];
    snprintf(shm_kv, sizeof(shm_kv), "SHADOW_TPU_SHM=%s", shm ? shm : "");
    snprintf(pre_kv, sizeof(pre_kv), "LD_PRELOAD=%s", preload ? preload : "");
    int j = 0;
    for (int i = 0; i < n; i++) {
        if (strncmp(envp[i], "SHADOW_TPU_SHM=", 15) == 0) continue;
        if (strncmp(envp[i], "LD_PRELOAD=", 11) == 0) continue;
        nenv[j++] = envp[i];
    }
    if (shm) nenv[j++] = shm_kv;
    if (preload) nenv[j++] = pre_kv;
    nenv[j] = NULL;
    int r = real_execve(path, argv, nenv);
    free(nenv); /* only reached on failure */
    return r;
}

int execve(const char *path, char *const argv[], char *const envp[]) {
    return shim_execve(path, argv, envp);
}

int execv(const char *path, char *const argv[]) {
    extern char **environ;
    return shim_execve(path, argv, environ);
}

int execvp(const char *file, char *const argv[]) {
    /* resolve via PATH the way libc would, then run our env-fixed exec */
    extern char **environ;
    if (strchr(file, '/')) return shim_execve(file, argv, environ);
    const char *pathv = getenv("PATH");
    if (!pathv) pathv = "/bin:/usr/bin";
    char buf[4096];
    const char *p = pathv;
    while (*p) {
        const char *colon = strchr(p, ':');
        size_t len = colon ? (size_t)(colon - p) : strlen(p);
        if (len + strlen(file) + 2 < sizeof(buf)) {
            memcpy(buf, p, len);
            buf[len] = '/';
            strcpy(buf + len + 1, file);
            if (access(buf, X_OK) == 0) return shim_execve(buf, argv, environ);
        }
        if (!colon) break;
        p = colon + 1;
    }
    errno = ENOENT;
    return -1;
}

/* uname: the nodename is the simulated hostname (apps commonly read it
 * instead of gethostname) */
#include <sys/utsname.h>

int uname(struct utsname *buf) {
    int r = (int)raw_uname_(buf);
    const char *simname = getenv("SHADOW_TPU_HOSTNAME");
    if (r == 0 && g_ready && simname) {
        snprintf(buf->nodename, sizeof(buf->nodename), "%s", simname);
    }
    return r;
}


/* msghdr I/O: simulated sockets flatten the iovec over the channel
 * (ancillary/control data is not carried — SCM_RIGHTS over a simulated
 * INET socket has no meaning); real fds keep the yield discipline. */
ssize_t recvmsg(int fd, struct msghdr *msg, int flags) {
    if (is_nlfd(fd)) {
        if (!msg || msg->msg_iovlen < 1) {
            errno = EFAULT;
            return -1;
        }
        socklen_t slen = msg->msg_namelen;
        size_t cap = msg->msg_iov[0].iov_len;
        /* ask for the FULL length (netlink always reports truncation in
         * msg_flags, whether or not the caller passed MSG_TRUNC) */
        ssize_t r = nl_recv(fd, msg->msg_iov[0].iov_base, cap,
                            flags | MSG_TRUNC,
                            (struct sockaddr *)msg->msg_name,
                            msg->msg_name ? &slen : NULL);
        if (r >= 0) {
            if (msg->msg_name) msg->msg_namelen = slen;
            msg->msg_controllen = 0;
            msg->msg_flags = (size_t)r > cap ? MSG_TRUNC : 0;
            if (!(flags & MSG_TRUNC) && (size_t)r > cap)
                r = (ssize_t)cap;
        }
        return r;
    }
    if (is_vfd(fd)) {
        if (!msg) {
            errno = EFAULT;
            return -1;
        }
        ssize_t total = iov_total(msg->msg_iov, (int)msg->msg_iovlen);
        if (total < 0) {
            errno = EINVAL;
            return -1;
        }
        int single = msg->msg_iovlen == 1; /* common case: no bounce copy */
        char *buf = single ? msg->msg_iov[0].iov_base
                           : malloc(total > 0 ? (size_t)total : 1);
        if (!buf && !single) {
            errno = ENOMEM;
            return -1;
        }
        socklen_t slen = msg->msg_namelen;
        int trunc = 0;
        ssize_t r = vfd_recvfrom(fd, buf, (size_t)total, flags,
                                 (struct sockaddr *)msg->msg_name,
                                 msg->msg_name ? &slen : NULL, &trunc);
        if (r >= 0) {
            if (!single)
                iov_scatter(msg->msg_iov, (int)msg->msg_iovlen, buf,
                            (size_t)r);
            if (msg->msg_name) msg->msg_namelen = slen;
            msg->msg_controllen = 0;
            msg->msg_flags = trunc ? MSG_TRUNC : 0;
        }
        if (!single) free(buf);
        return r;
    }
    maybe_yield(fd, POLLIN, flags & MSG_DONTWAIT);
    return (ssize_t)raw_recvmsg(fd, msg, flags);
}

ssize_t sendmsg(int fd, const struct msghdr *msg, int flags) {
    if (is_nlfd(fd)) {
        if (!msg || msg->msg_iovlen < 1) {
            errno = EFAULT;
            return -1;
        }
        return nl_send(fd, msg->msg_iov[0].iov_base,
                       msg->msg_iov[0].iov_len);
    }
    if (is_vfd(fd)) {
        if (!msg) {
            errno = EFAULT;
            return -1;
        }
        uint32_t ip = 0;
        uint16_t port = 0;
        if (msg->msg_name &&
            addr_to_ip_port(msg->msg_name, msg->msg_namelen, &ip, &port) != 0)
            return -1;
        ssize_t total = iov_total(msg->msg_iov, (int)msg->msg_iovlen);
        if (total < 0) {
            errno = EINVAL;
            return -1;
        }
        if (msg->msg_iovlen == 1)
            return vfd_sendto(fd, msg->msg_iov[0].iov_base, (size_t)total,
                              flags, ip, port);
        char *buf = malloc(total > 0 ? (size_t)total : 1);
        if (!buf) {
            errno = ENOMEM;
            return -1;
        }
        iov_gather(msg->msg_iov, (int)msg->msg_iovlen, buf);
        ssize_t r = vfd_sendto(fd, buf, (size_t)total, flags, ip, port);
        free(buf);
        return r;
    }
    maybe_yield(fd, POLLOUT, flags & MSG_DONTWAIT);
    return (ssize_t)raw_sendmsg(fd, msg, flags);
}

ssize_t writev(int fd, const struct iovec *iov, int iovcnt) {
    if (!is_vfd(fd)) {
        maybe_yield(fd, POLLOUT, 0);
        ssize_t r = (ssize_t)raw_writev(fd, iov, iovcnt);
        if (r > 0) meta_note_write(fd);
        return r;
    }
    ssize_t total = iov_total(iov, iovcnt);
    if (total < 0) {
        errno = EINVAL;
        return -1;
    }
    if (iovcnt == 1)
        return vfd_sendto(fd, iov[0].iov_base, (size_t)total, 0, 0, 0);
    char *buf = malloc(total > 0 ? (size_t)total : 1);
    if (!buf) {
        errno = ENOMEM;
        return -1;
    }
    iov_gather(iov, iovcnt, buf);
    ssize_t r = vfd_sendto(fd, buf, (size_t)total, 0, 0, 0);
    free(buf);
    return r;
}

ssize_t readv(int fd, const struct iovec *iov, int iovcnt) {
    if (!is_vfd(fd)) {
        maybe_yield(fd, POLLIN, 0);
        return (ssize_t)raw_readv(fd, iov, iovcnt);
    }
    ssize_t total = iov_total(iov, iovcnt);
    if (total < 0) {
        errno = EINVAL;
        return -1;
    }
    if (iovcnt == 1)
        return vfd_recvfrom(fd, iov[0].iov_base, (size_t)total, 0, NULL,
                            NULL, NULL);
    char *buf = malloc(total > 0 ? (size_t)total : 1);
    if (!buf) {
        errno = ENOMEM;
        return -1;
    }
    ssize_t r = vfd_recvfrom(fd, buf, (size_t)total, 0, NULL, NULL, NULL);
    if (r > 0) iov_scatter(iov, iovcnt, buf, (size_t)r);
    free(buf);
    return r;
}

/* dup family: duplicating a simulated socket registers the new fd number
 * as an alias of the same manager-side socket (refcounted, like fork
 * inheritance).  O_NONBLOCK is copied at dup time — it nominally lives on
 * the shared open file description, a divergence only visible to apps
 * that F_SETFL one alias and expect the other to change. */
static int vfd_dup_common(int oldfd, int newfd) {
    int64_t args[6] = {oldfd, newfd, 0, 0, 0, 0};
    int64_t ret = shim_call(SHIM_OP_DUP, args, NULL, 0, NULL, NULL, NULL);
    if (ret < 0) {
        real_close(newfd);
        errno = (int)-ret;
        return -1;
    }
    vfd_register(newfd, vfd_nonblock[oldfd], vfd_stream[oldfd]);
    vfd_listening[newfd] = vfd_listening[oldfd];
    return newfd;
}

int dup(int oldfd) {
#define real_dup(fd) ((int)raw_dup(fd))
    if (is_vfd(oldfd)) {
        int fd = reserve_fd();
        if (fd < 0) return -1;
        return vfd_dup_common(oldfd, fd);
    }
    int fd = real_dup(oldfd);
    if (fd >= 0 && fd < SHIM_MAX_FDS) fd_fifo_cache[fd] = 0;
    return fd;
#undef real_dup
}

int dup2(int oldfd, int newfd) {
#define real_dup2(a, b) ((int)raw_dup2_(a, b))
    if (is_vfd(oldfd)) {
        if (oldfd == newfd) return newfd;
        if (newfd < 0 || newfd >= SHIM_MAX_FDS) {
            errno = EBADF;
            return -1;
        }
        close(newfd); /* interposed: handles sim and real targets alike */
        /* occupy newfd with an O_PATH reservation at that exact number;
         * keep it CLOEXEC so the stub cannot leak into an exec'd image
         * (simulated sockets never survive exec anyway).  newfd is free
         * now, so open() may hand back newfd ITSELF — then the
         * reservation is already in place and dup2/close would destroy
         * it (dup2(fd,fd) is a no-op, the close frees the number) */
        int tmp = open("/dev/null", O_PATH | O_CLOEXEC);
        if (tmp < 0) return -1;
        if (tmp != newfd) {
            int r = real_dup2(tmp, newfd);
            real_close(tmp);
            if (r < 0) return -1;
            real_fcntl(newfd, F_SETFD, FD_CLOEXEC);
        }
        return vfd_dup_common(oldfd, newfd);
    }
    if (is_vfd(newfd)) close(newfd); /* real replaces a simulated socket */
    int fd = real_dup2(oldfd, newfd);
    if (fd >= 0 && fd < SHIM_MAX_FDS) fd_fifo_cache[fd] = 0;
    fd_meta_reset(fd);
    if (fd >= 0 && g_ready) epoll_forget_fd(fd);
    return fd;
#undef real_dup2
}

int dup3(int oldfd, int newfd, int flags) {
#define real_dup3(a, b, c) ((int)raw_dup3_(a, b, c))
    if (is_vfd(oldfd)) {
        if (oldfd == newfd) {
            errno = EINVAL; /* dup3 rejects equal fds, unlike dup2 */
            return -1;
        }
        return dup2(oldfd, newfd); /* CLOEXEC: vfds die at exec anyway */
    }
    if (is_vfd(newfd)) close(newfd);
    int fd = real_dup3(oldfd, newfd, flags);
    if (fd >= 0 && fd < SHIM_MAX_FDS) fd_fifo_cache[fd] = 0;
    fd_meta_reset(fd);
    if (fd >= 0 && g_ready) epoll_forget_fd(fd);
    return fd;
#undef real_dup3
}

/* ------------------------------------------------- raw-syscall dispatch */

/* Raw futex virtualization (the manager-side futex table, the reference's
 * host/futex_table.rs + handler/futex.rs).  Strict turn-taking makes the
 * classic check-then-park race vanish: no other simulation thread runs
 * between this thread's value check and the manager parking it, so the
 * shim can test *uaddr locally (same address space) and ship only the
 * park/wake to the manager.  PI/robust variants are not virtualized —
 * they re-execute natively (glibc's pthread surface is interposed at
 * symbol level, so only exotic direct users reach them). */
#include <sched.h>

static long shim_futex_emu(long uaddr, long op, long val, long timeout,
                           long uaddr2, long val3) {
    /* t_exit_sent: this thread already told the manager it is gone (its
     * channel is retired); glibc's thread-teardown futexes — e.g. the
     * main thread parking forever inside pthread_exit — must block
     * NATIVELY, which is exactly their purpose */
    if (!g_ready || !uaddr || t_exit_sent)
        return shim_raw_syscall6(SYS_futex, uaddr, op, val, timeout, uaddr2,
                                 val3);
    int cmd = (int)(op & FUTEX_CMD_MASK);
    switch (cmd) {
        case FUTEX_WAIT:
        case FUTEX_WAIT_BITSET: {
            if (__atomic_load_n((uint32_t *)uaddr, __ATOMIC_SEQ_CST) !=
                (uint32_t)val)
                return -EAGAIN;
            int64_t tns = -1;
            const struct timespec *ts = (const struct timespec *)timeout;
            if (ts) {
                tns = (int64_t)ts->tv_sec * 1000000000ll + ts->tv_nsec;
                if (cmd == FUTEX_WAIT_BITSET) {
                    /* BITSET waits take an absolute deadline (monotonic or
                     * realtime — both are the one simulated clock) */
                    tns -= (int64_t)sim_now_ns();
                    if (tns < 0) tns = 0;
                }
            }
            uint32_t bs =
                cmd == FUTEX_WAIT_BITSET ? (uint32_t)val3 : 0xFFFFFFFFu;
            int64_t args[6] = {uaddr, tns, (int64_t)bs, 0, 0, 0};
            return shim_call(SHIM_OP_FUTEX_WAIT, args, NULL, 0, NULL, NULL,
                             NULL);
        }
        case FUTEX_WAKE:
        case FUTEX_WAKE_BITSET: {
            uint32_t bs =
                cmd == FUTEX_WAKE_BITSET ? (uint32_t)val3 : 0xFFFFFFFFu;
            int64_t args[6] = {uaddr, val, (int64_t)bs, 0, 0, 0};
            return shim_call(SHIM_OP_FUTEX_WAKE, args, NULL, 0, NULL, NULL,
                             NULL);
        }
        case FUTEX_CMP_REQUEUE:
            if (__atomic_load_n((uint32_t *)uaddr, __ATOMIC_SEQ_CST) !=
                (uint32_t)val3)
                return -EAGAIN;
            /* fall through */
        case FUTEX_REQUEUE: {
            /* for requeue ops the timeout argument slot carries val2 =
             * max threads to requeue.  Linux returns woken+requeued for
             * CMP_REQUEUE but only woken for plain REQUEUE. */
            int64_t args[6] = {uaddr, val, uaddr2, timeout, 0, 0};
            int64_t reply[6];
            int64_t woken = shim_call(SHIM_OP_FUTEX_REQUEUE, args, NULL, 0,
                                      NULL, NULL, reply);
            if (woken < 0) return woken;
            return cmd == FUTEX_CMP_REQUEUE ? woken + reply[1] : woken;
        }
        case FUTEX_WAKE_OP: {
            /* modify *uaddr2 locally (turn-taking = no concurrent
             * mutators), wake uaddr, conditionally wake uaddr2 */
            uint32_t enc = (uint32_t)val3;
            int op_ = (enc >> 28) & 0xF;
            int cmp_ = (enc >> 24) & 0xF;
            /* 12-bit fields are sign-extended, as the kernel does
             * (sign_extend32(..., 11)) */
            int32_t oparg = (int32_t)((enc >> 12) & 0xFFF);
            int32_t cmparg = (int32_t)(enc & 0xFFF);
            oparg = (oparg << 20) >> 20;
            cmparg = (cmparg << 20) >> 20;
            if (op_ & 8) oparg = 1 << (oparg & 31); /* FUTEX_OP_ARG_SHIFT */
            uint32_t *p2 = (uint32_t *)uaddr2;
            if (!p2) return -EFAULT;
            uint32_t old = *p2;
            switch (op_ & 7) {
                case 0: *p2 = (uint32_t)oparg; break;        /* SET */
                case 1: *p2 = old + (uint32_t)oparg; break;  /* ADD */
                case 2: *p2 = old | (uint32_t)oparg; break;  /* OR */
                case 3: *p2 = old & ~(uint32_t)oparg; break; /* ANDN */
                case 4: *p2 = old ^ (uint32_t)oparg; break;  /* XOR */
            }
            int64_t args[6] = {uaddr, val, 0xFFFFFFFFll, 0, 0, 0};
            long woken =
                shim_call(SHIM_OP_FUTEX_WAKE, args, NULL, 0, NULL, NULL, NULL);
            int hit;
            switch (cmp_) {
                case 0: hit = old == (uint32_t)cmparg; break; /* EQ */
                case 1: hit = old != (uint32_t)cmparg; break; /* NE */
                case 2: hit = old < (uint32_t)cmparg; break;  /* LT */
                case 3: hit = old <= (uint32_t)cmparg; break; /* LE */
                case 4: hit = old > (uint32_t)cmparg; break;  /* GT */
                case 5: hit = old >= (uint32_t)cmparg; break; /* GE */
                default: hit = 0;
            }
            if (hit) {
                int64_t args2[6] = {uaddr2, timeout, 0xFFFFFFFFll, 0, 0, 0};
                long w2 = shim_call(SHIM_OP_FUTEX_WAKE, args2, NULL, 0, NULL,
                                    NULL, NULL);
                if (w2 > 0) woken += w2;
            }
            return woken;
        }
        default:
            return shim_raw_syscall6(SYS_futex, uaddr, op, val, timeout,
                                     uaddr2, val3);
    }
}

/* ------------------------------------------------------------------ */
/* Simulated file metadata (hermeticity).  The reference virtualizes the
 * file layer in its descriptor table (src/main/host/descriptor/
 * regular_file.c: timestamps on the simulated clock); this shim keeps
 * files native but SCRUBS every wall-clock-derived byte out of what the
 * plugin can observe:
 *
 * - stat family: atime/mtime/ctime are the sim time of the last write
 *   the simulation made to that inode (tracked below), or the simulation
 *   epoch (2000-01-01) for files it never wrote;
 * - getdents64: entries sorted by name (host readdir order is
 *   filesystem-state dependent);
 * - sysinfo + /proc/uptime: uptime from the simulated clock, loads and
 *   memory figures fixed constants;
 * - sched_getaffinity: the modeled 1-CPU set (cpu 0), matching
 *   vdso_repl_getcpu.
 *
 * Write tracking is per-process (the shim sees this process's writes);
 * cross-process mtime propagation would need the manager-side file table
 * the reference has — documented limitation. */

#include <sys/sysinfo.h>
#include <sys/statfs.h>
#include <sys/times.h>

#define SHIM_SIM_EPOCH_NS 946684800000000000ull /* 2000-01-01T00:00:00Z */

/* inode -> last-write sim time, open-addressed (sim threads are
 * turn-taking, so no lock) */
#define META_SLOTS 1024
static struct { uint64_t key; uint64_t wns; } meta_tab[META_SLOTS];

static uint64_t meta_key(uint64_t dev, uint64_t ino) {
    uint64_t k = dev * 0x9E3779B97F4A7C15ull ^ ino;
    return k ? k : 1; /* 0 marks an empty slot */
}

static void meta_note(uint64_t dev, uint64_t ino, uint64_t ns) {
    uint64_t k = meta_key(dev, ino);
    size_t i = (size_t)(k % META_SLOTS);
    for (size_t probe = 0; probe < META_SLOTS; probe++) {
        size_t s = (i + probe) % META_SLOTS;
        if (meta_tab[s].key == k || meta_tab[s].key == 0) {
            meta_tab[s].key = k;
            meta_tab[s].wns = ns;
            return;
        }
    }
    /* table full: overwrite the home slot (bounded, deterministic) */
    meta_tab[i].key = k;
    meta_tab[i].wns = ns;
}

static int meta_get(uint64_t dev, uint64_t ino, uint64_t *ns) {
    uint64_t k = meta_key(dev, ino);
    size_t i = (size_t)(k % META_SLOTS);
    for (size_t probe = 0; probe < META_SLOTS; probe++) {
        size_t s = (i + probe) % META_SLOTS;
        if (meta_tab[s].key == 0) return 0;
        if (meta_tab[s].key == k) {
            *ns = meta_tab[s].wns;
            return 1;
        }
    }
    return 0;
}

/* a deleted/replaced file's inode may be reused by the host fs for an
 * unrelated new file; mapping it back to the epoch (rather than slot
 * deletion, which open addressing complicates) removes the
 * host-allocation-dependent resurrection of the old write time */
static void meta_forget(uint64_t dev, uint64_t ino) {
    uint64_t k = meta_key(dev, ino);
    size_t i = (size_t)(k % META_SLOTS);
    for (size_t probe = 0; probe < META_SLOTS; probe++) {
        size_t s = (i + probe) % META_SLOTS;
        if (meta_tab[s].key == 0) return;
        if (meta_tab[s].key == k) {
            meta_tab[s].wns = SHIM_SIM_EPOCH_NS;
            return;
        }
    }
}

/* forget by path (pre-unlink/pre-rename-destination): resolve the inode
 * about to become free */
static void meta_forget_path(int dirfd, const char *path, int flags) {
    if (!g_shm || !path) return;
    struct stat st;
    long r = shim_raw_syscall6(SYS_newfstatat, dirfd, (long)path, (long)&st,
                              flags | AT_SYMLINK_NOFOLLOW, 0, 0);
    if (r == 0) meta_forget((uint64_t)st.st_dev, (uint64_t)st.st_ino);
}

/* utimensat/futimens: the app set explicit timestamps — record the SET
 * mtime so later stats reflect it (UTIME_NOW resolves to the SIMULATED
 * clock; letting the kernel's wall-clock value stand would leak).  The
 * kernel call still runs (permissions/errno), its wall times are then
 * shadowed by this table. */
static void meta_note_utimens(int dirfd, const char *path,
                              const struct timespec *times, int flags) {
    if (!g_shm) return;
    uint64_t dev, ino;
    struct stat st;
    long r;
    if (path)
        r = shim_raw_syscall6(SYS_newfstatat, dirfd, (long)path, (long)&st,
                              flags, 0, 0);
    else
        r = shim_raw_syscall6(SYS_fstat, dirfd, (long)&st, 0, 0, 0, 0);
    if (r != 0) return;
    dev = (uint64_t)st.st_dev;
    ino = (uint64_t)st.st_ino;
    if (!times) {
        meta_note(dev, ino, sim_now_ns());
        return;
    }
    const struct timespec *mt = &times[1];
    if (mt->tv_nsec == UTIME_OMIT) return;
    if (mt->tv_nsec == UTIME_NOW)
        meta_note(dev, ino, sim_now_ns());
    else
        meta_note(dev, ino, (uint64_t)mt->tv_sec * 1000000000ull +
                                (uint64_t)mt->tv_nsec);
}

/* per-fd (dev, ino) cache so write tracking costs one fstat per fd
 * lifetime, not one per write */
static uint8_t fd_meta_state[SHIM_MAX_FDS]; /* 0 unknown, 1 reg, 2 other */
static uint64_t fd_meta_dev[SHIM_MAX_FDS];
static uint64_t fd_meta_ino[SHIM_MAX_FDS];

static void fd_meta_reset(int fd) {
    if (fd >= 0 && fd < SHIM_MAX_FDS) fd_meta_state[fd] = 0;
}

static void meta_note_write(int fd) {
    if (!g_shm || fd < 0 || fd >= SHIM_MAX_FDS) return;
    if (fd_meta_state[fd] == 0) {
        struct stat st;
        long r = shim_raw_syscall6(SYS_fstat, fd, (long)&st, 0, 0, 0, 0);
        if (r == 0 && (S_ISREG(st.st_mode) || S_ISDIR(st.st_mode))) {
            fd_meta_state[fd] = 1;
            fd_meta_dev[fd] = (uint64_t)st.st_dev;
            fd_meta_ino[fd] = (uint64_t)st.st_ino;
        } else {
            fd_meta_state[fd] = 2;
        }
    }
    if (fd_meta_state[fd] == 1)
        meta_note(fd_meta_dev[fd], fd_meta_ino[fd], sim_now_ns());
}

static void meta_set_times(uint64_t dev, uint64_t ino, uint64_t mode,
                           int64_t *sec_out, int64_t *nsec_out) {
    uint64_t ns = SHIM_SIM_EPOCH_NS;
    (void)mode;
    meta_get(dev, ino, &ns);
    *sec_out = (int64_t)(ns / 1000000000ull);
    *nsec_out = (int64_t)(ns % 1000000000ull);
}

static void scrub_stat(struct stat *st) {
    if (!st || !g_shm) return;
    int64_t sec, nsec;
    meta_set_times((uint64_t)st->st_dev, (uint64_t)st->st_ino,
                   (uint64_t)st->st_mode, &sec, &nsec);
    st->st_atim.tv_sec = st->st_mtim.tv_sec = st->st_ctim.tv_sec =
        (time_t)sec;
    st->st_atim.tv_nsec = st->st_mtim.tv_nsec = st->st_ctim.tv_nsec =
        (long)nsec;
}

static void scrub_statx(struct statx *sx) {
    if (!sx || !g_shm) return;
    int64_t sec, nsec;
    meta_set_times(((uint64_t)sx->stx_dev_major << 32) | sx->stx_dev_minor,
                   sx->stx_ino, sx->stx_mode, &sec, &nsec);
    sx->stx_atime.tv_sec = sx->stx_btime.tv_sec = sx->stx_ctime.tv_sec =
        sx->stx_mtime.tv_sec = sec;
    sx->stx_atime.tv_nsec = sx->stx_btime.tv_nsec = sx->stx_ctime.tv_nsec =
        sx->stx_mtime.tv_nsec = (uint32_t)nsec;
}

/* getdents64: pin directory enumeration order (sort by name).  The
 * kernel-side count is clamped to DENTS_BYTES so every batch fits the
 * static scratch (the SIGSYS path runs on the interrupted thread's
 * stack — goroutine stacks can be ~8 KiB, so NO large frames here; the
 * scratch is static under a spinlock).  Order is deterministic per
 * batch; directories whose enumeration spans several 120 KiB batches
 * (several thousand entries) are only per-batch sorted — documented
 * limitation (the reference virtualizes enumeration wholesale in its
 * descriptor layer, handler/mod.rs getdents).  d_off values ride along
 * with their entries — seekdir across a sorted batch is unsupported. */
struct shim_dirent64 {
    uint64_t d_ino;
    int64_t d_off;
    unsigned short d_reclen;
    unsigned char d_type;
    char d_name[];
};

#define DENTS_BYTES (120 * 1024)
#define DENTS_MAX (DENTS_BYTES / 24 + 64) /* min reclen is 24 bytes */
static char dents_tmp[DENTS_BYTES];
static struct shim_dirent64 *dents_ents[DENTS_MAX];
static int dents_lock; /* raw spinlock: the scratch is shared */

static void dents_acquire(void) {
    while (__atomic_exchange_n(&dents_lock, 1, __ATOMIC_ACQUIRE))
        shim_raw_syscall6(SYS_sched_yield, 0, 0, 0, 0, 0, 0);
}

static void dents_release(void) {
    __atomic_store_n(&dents_lock, 0, __ATOMIC_RELEASE);
}

static long scrub_getdents(char *buf, long n) {
    dents_acquire();
    struct shim_dirent64 **ents = dents_ents;
    int cnt = 0;
    long off = 0;
    while (off < n && cnt < DENTS_MAX) {
        struct shim_dirent64 *d = (struct shim_dirent64 *)(buf + off);
        if (d->d_reclen == 0) break;
        ents[cnt++] = d;
        off += d->d_reclen;
    }
    if (off != n || cnt >= DENTS_MAX) {
        dents_release();
        return n; /* malformed batch: leave as-is */
    }
    /* insertion sort by name (batches are small; deterministic) */
    for (int i = 1; i < cnt; i++) {
        struct shim_dirent64 *key = ents[i];
        int j = i - 1;
        while (j >= 0 && strcmp(ents[j]->d_name, key->d_name) > 0) {
            ents[j + 1] = ents[j];
            j--;
        }
        ents[j + 1] = key;
    }
    /* rewrite the batch in sorted order through the bounce buffer */
    long w = 0;
    for (int i = 0; i < cnt; i++) {
        memcpy(dents_tmp + w, ents[i], ents[i]->d_reclen);
        w += ents[i]->d_reclen;
    }
    memcpy(buf, dents_tmp, (size_t)w);
    dents_release();
    return n;
}

static long emu_sysinfo(struct sysinfo *si) {
    if (!si) return -EFAULT;
    memset(si, 0, sizeof(*si));
    uint64_t now = sim_now_ns();
    si->uptime = (long)((now - SHIM_SIM_EPOCH_NS) / 1000000000ull);
    /* loads zero; fixed modeled memory figures (16 GiB total, half free) */
    si->totalram = 16ull << 30;
    si->freeram = 8ull << 30;
    si->bufferram = 0;
    si->totalswap = 0;
    si->freeswap = 0;
    si->procs = 16;
    si->mem_unit = 1;
    return 0;
}

/* /proc/{uptime,loadavg,meminfo,stat,cpuinfo} synthesized from modeled
 * state: opening one returns a memfd pre-filled at the open instant
 * (read offsets behave normally; the file does not tick while open —
 * matching a single read() snapshot, which is how real consumers use
 * them).  Values agree with the other virtualized views: 1 CPU (getcpu/
 * affinity), 16 GiB total / 8 GiB free (sysinfo/statfs), sim uptime. */
static long proc_synth_fd(const char *text, int len) {
    long fd = shim_raw_syscall6(SYS_memfd_create, (long)"sim_proc", 0, 0,
                               0, 0, 0);
    if (fd < 0) return -1;
    if (shim_raw_syscall6(SYS_write, fd, (long)text, len, 0, 0, 0) != len) {
        shim_raw_syscall6(SYS_close, fd, 0, 0, 0, 0, 0);
        return -1; /* fall through to the real file, never truncated synth */
    }
    shim_raw_syscall6(SYS_lseek, fd, 0, 0 /* SEEK_SET */, 0, 0, 0);
    return fd;
}

static long maybe_open_synth_proc(const char *path, long flags) {
    if (!g_shm || !path) return -1;
    if ((flags & O_ACCMODE) != O_RDONLY)
        return -1; /* the kernel refuses write opens of these; so do we */
    char buf[512];
    int len;
    if (strcmp(path, "/proc/uptime") == 0) {
        uint64_t up =
            (sim_now_ns() - SHIM_SIM_EPOCH_NS) / 10000000ull; /* cs */
        len = snprintf(buf, sizeof(buf), "%llu.%02llu %llu.%02llu\n",
                       (unsigned long long)(up / 100),
                       (unsigned long long)(up % 100),
                       (unsigned long long)(up / 100),
                       (unsigned long long)(up % 100));
    } else if (strcmp(path, "/proc/loadavg") == 0) {
        len = snprintf(buf, sizeof(buf),
                       "0.00 0.00 0.00 1/16 2\n");
    } else if (strcmp(path, "/proc/meminfo") == 0) {
        len = snprintf(buf, sizeof(buf),
                       "MemTotal:       16777216 kB\n"
                       "MemFree:         8388608 kB\n"
                       "MemAvailable:    8388608 kB\n"
                       "Buffers:               0 kB\n"
                       "Cached:                0 kB\n"
                       "SwapTotal:             0 kB\n"
                       "SwapFree:              0 kB\n");
    } else if (strcmp(path, "/proc/stat") == 0) {
        uint64_t ticks =
            (sim_now_ns() - SHIM_SIM_EPOCH_NS) / 10000000ull; /* HZ=100 */
        len = snprintf(buf, sizeof(buf),
                       "cpu  %llu 0 0 0 0 0 0 0 0 0\n"
                       "cpu0 %llu 0 0 0 0 0 0 0 0 0\n"
                       "ctxt 0\nbtime 946684800\nprocesses 2\n"
                       "procs_running 1\nprocs_blocked 0\n",
                       (unsigned long long)ticks,
                       (unsigned long long)ticks);
    } else if (strcmp(path, "/proc/cpuinfo") == 0) {
        len = snprintf(buf, sizeof(buf),
                       "processor\t: 0\n"
                       "vendor_id\t: SimulatedCPU\n"
                       "model name\t: shadow-tpu modeled core\n"
                       "cpu MHz\t\t: 1000.000\n"
                       "cache size\t: 1024 KB\n"
                       "cpu cores\t: 1\n"
                       "bogomips\t: 2000.00\n\n");
    } else {
        return -1;
    }
    if (len < 0 || len >= (int)sizeof(buf)) return -1;
    return proc_synth_fd(buf, len);
}

/* Adapter: the public wrappers use libc conventions (-1 + errno); the
 * trapped register must carry -errno. */
#define WRAPRET(expr)                                                        \
    do {                                                                     \
        errno = 0;                                                           \
        long wr_ = (long)(expr);                                             \
        return wr_ < 0 && errno ? -(long)errno : wr_;                        \
    } while (0)

/* WRAPRET without the return: for cases that must clean up first */
#define WRAPSET(out, expr)                                                   \
    do {                                                                     \
        errno = 0;                                                           \
        long wr_ = (long)(expr);                                             \
        (out) = wr_ < 0 && errno ? -(long)errno : wr_;                       \
    } while (0)

/* The syscall-user-dispatch backstop routes EVERY syscall issued outside
 * the shim's text here.  Simulation-owned calls reuse the exact logic of
 * the LD_PRELOAD wrappers above (which themselves fall back to raw kernel
 * calls for fds the simulation does not own), so raw-syscall binaries —
 * the reference's Go-runtime scenario (src/test/golang/,
 * preload-libc/gen_syscall_wrappers_c.py) — see the same semantics
 * libc-calling binaries see.  `*handled = 0` sends anything else to the
 * kernel unchanged. */
static long emu_owned_syscall(long nr, long a1, long a2, long a3, long a4,
                              long a5, long a6, int *handled) {
    *handled = 1;
    switch (nr) {
        /* ---- time / sleep / entropy (also the legacy-seccomp trap set;
         * never re-executed natively: under a stale pre-exec filter the
         * re-execution would re-trap) ---- */
        case SYS_clock_gettime:
            return vdso_repl_clock_gettime((clockid_t)a1,
                                           (struct timespec *)a2);
        case SYS_gettimeofday:
            return vdso_repl_gettimeofday((struct timeval *)a1, (void *)a2);
        case SYS_time:
            return vdso_repl_time((time_t *)a1);
        case SYS_nanosleep:
        case SYS_clock_nanosleep: {
            const struct timespec *req;
            struct timespec *rem;
            if (nr == SYS_nanosleep) {
                req = (const struct timespec *)a1;
                rem = (struct timespec *)a2;
            } else {
                req = (const struct timespec *)a3;
                rem = (struct timespec *)a4;
            }
            if (!req) return -EFAULT;
            int64_t ns = (int64_t)req->tv_sec * 1000000000ll + req->tv_nsec;
            if (nr == SYS_clock_nanosleep && (a2 & 1 /* TIMER_ABSTIME */)) {
                ns -= (int64_t)sim_now_ns();
                if (ns < 0) ns = 0;
            }
            if (g_ready) {
                int64_t args[6] = {ns, 0, 0, 0, 0, 0};
                shim_call(SHIM_OP_NANOSLEEP, args, NULL, 0, NULL, NULL, NULL);
            } /* else: dying process, nobody services the channel */
            if (rem && nr == SYS_nanosleep) {
                rem->tv_sec = 0;
                rem->tv_nsec = 0;
            }
            return 0;
        }
        case SYS_getrandom: {
            uint8_t *p = (uint8_t *)a1;
            size_t left = (size_t)a2;
            if (!p && left) return -EFAULT;
            fill_entropy(p, left);
            return (long)left;
        }

        /* ---- sockets ---- */
        case SYS_socket:
            WRAPRET(socket((int)a1, (int)a2, (int)a3));
        case SYS_bind:
            WRAPRET(bind((int)a1, (const struct sockaddr *)a2,
                         (socklen_t)a3));
        case SYS_connect:
            WRAPRET(connect((int)a1, (const struct sockaddr *)a2,
                            (socklen_t)a3));
        case SYS_listen:
            WRAPRET(listen((int)a1, (int)a2));
        case SYS_accept:
            WRAPRET(accept((int)a1, (struct sockaddr *)a2, (socklen_t *)a3));
        case SYS_accept4:
            WRAPRET(accept4((int)a1, (struct sockaddr *)a2, (socklen_t *)a3,
                            (int)a4));
        case SYS_sendto:
            WRAPRET(sendto((int)a1, (const void *)a2, (size_t)a3, (int)a4,
                           (const struct sockaddr *)a5, (socklen_t)a6));
        case SYS_recvfrom:
            WRAPRET(recvfrom((int)a1, (void *)a2, (size_t)a3, (int)a4,
                             (struct sockaddr *)a5, (socklen_t *)a6));
        case SYS_sendmsg:
            WRAPRET(sendmsg((int)a1, (const struct msghdr *)a2, (int)a3));
        case SYS_recvmsg:
            WRAPRET(recvmsg((int)a1, (struct msghdr *)a2, (int)a3));
        case SYS_shutdown:
            WRAPRET(shutdown((int)a1, (int)a2));
        case SYS_getsockname:
            WRAPRET(getsockname((int)a1, (struct sockaddr *)a2,
                                (socklen_t *)a3));
        case SYS_getpeername:
            WRAPRET(getpeername((int)a1, (struct sockaddr *)a2,
                                (socklen_t *)a3));
        case SYS_setsockopt:
            WRAPRET(setsockopt((int)a1, (int)a2, (int)a3, (const void *)a4,
                               (socklen_t)a5));
        case SYS_getsockopt:
            WRAPRET(getsockopt((int)a1, (int)a2, (int)a3, (void *)a4,
                               (socklen_t *)a5));

        /* ---- fd I/O that may hit simulated fds (the wrappers fall back
         * to raw kernel calls — with the pipe/fifo sim-yield discipline —
         * for real fds) ---- */
        case SYS_read:
            WRAPRET(read((int)a1, (void *)a2, (size_t)a3));
        case SYS_write:
            WRAPRET(write((int)a1, (const void *)a2, (size_t)a3));
        case SYS_readv:
            WRAPRET(readv((int)a1, (const struct iovec *)a2, (int)a3));
        case SYS_writev:
            WRAPRET(writev((int)a1, (const struct iovec *)a2, (int)a3));
        case SYS_close:
            WRAPRET(close((int)a1));
        case SYS_dup:
            WRAPRET(dup((int)a1));
        case SYS_dup2:
            WRAPRET(dup2((int)a1, (int)a2));
        case SYS_dup3:
            WRAPRET(dup3((int)a1, (int)a2, (int)a3));
        case SYS_fcntl:
            WRAPRET(fcntl((int)a1, (int)a2, a3));
        case SYS_ioctl:
            WRAPRET(ioctl((int)a1, (unsigned long)a2, a3));

        /* ---- readiness ---- */
        case SYS_poll:
            WRAPRET(poll((struct pollfd *)a1, (nfds_t)a2, (int)a3));
        case SYS_ppoll: {
            /* the raw sigmask arg is honored: wait_mask semantics inside
             * the libc-level wrapper (a4 = kernel sigset, a5 = size) */
            wait_mask_t w;
            wait_mask_enter((const void *)a4, (size_t)a5, &w);
            long r;
            WRAPSET(r, ppoll((struct pollfd *)a1, (nfds_t)a2,
                             (const struct timespec *)a3, NULL));
            wait_mask_leave(&w);
            return r;
        }
        case SYS_select:
            WRAPRET(select((int)a1, (fd_set *)a2, (fd_set *)a3, (fd_set *)a4,
                           (struct timeval *)a5));
        case SYS_pselect6: {
            const struct timespec *ts = (const struct timespec *)a5;
            struct timeval tv, *tvp = NULL;
            if (ts) {
                tv.tv_sec = ts->tv_sec;
                tv.tv_usec = (ts->tv_nsec + 999) / 1000;
                tvp = &tv;
            }
            /* a6 -> struct { const sigset_t *ss; size_t ss_len } */
            wait_mask_t w;
            w.active = 0;
            if (a6) {
                const struct {
                    const void *ss;
                    size_t ss_len;
                } *sx = (const void *)a6;
                wait_mask_enter(sx->ss, sx->ss_len, &w);
            }
            long r;
            WRAPSET(r, select((int)a1, (fd_set *)a2, (fd_set *)a3,
                              (fd_set *)a4, tvp));
            wait_mask_leave(&w);
            return r;
        }
        case SYS_epoll_ctl:
            WRAPRET(epoll_ctl((int)a1, (int)a2, (int)a3,
                              (struct epoll_event *)a4));
        case SYS_epoll_wait:
            WRAPRET(epoll_wait((int)a1, (struct epoll_event *)a2, (int)a3,
                               (int)a4));
        case SYS_epoll_pwait: {
            wait_mask_t w;
            wait_mask_enter((const void *)a5, (size_t)a6, &w);
            long r;
            WRAPSET(r, epoll_pwait((int)a1, (struct epoll_event *)a2,
                                   (int)a3, (int)a4, NULL));
            wait_mask_leave(&w);
            return r;
        }

        /* ---- inotify stubs ---- */
        case SYS_inotify_init:
            WRAPRET(inotify_init());
        case SYS_inotify_init1:
            WRAPRET(inotify_init1((int)a1));
        case SYS_inotify_add_watch:
            WRAPRET(inotify_add_watch((int)a1, (const char *)a2,
                                      (uint32_t)a3));
        case SYS_inotify_rm_watch:
            WRAPRET(inotify_rm_watch((int)a1, (int)a2));

        /* ---- virtual timerfd/eventfd ---- */
        case SYS_timerfd_create:
            WRAPRET(timerfd_create((int)a1, (int)a2));
        case SYS_timerfd_settime:
            WRAPRET(timerfd_settime((int)a1, (int)a2,
                                    (const struct itimerspec *)a3,
                                    (struct itimerspec *)a4));
        case SYS_timerfd_gettime:
            WRAPRET(timerfd_gettime((int)a1, (struct itimerspec *)a2));
        case SYS_eventfd:
            WRAPRET(eventfd((unsigned int)a1, 0));
        case SYS_eventfd2:
            WRAPRET(eventfd((unsigned int)a1, (int)a2));

        /* ---- futex ---- */
        case SYS_futex:
            return shim_futex_emu(a1, a2, a3, a4, a5, a6);

        /* ---- process lifecycle ---- */
        case SYS_fork:
        case SYS_vfork:
            if (t_in_fork) {
                long r = shim_raw_syscall6(nr, a1, a2, a3, a4, a5, a6);
                if (r == 0 && g_sud_on) sud_arm();
                return r;
            }
            WRAPRET(fork());
        case SYS_clone: {
            unsigned long fl = (unsigned long)a1;
            if (t_in_fork) {
                /* glibc's fork internals, reached through our wrapper: run
                 * the clone raw; on the child side dispatch was not
                 * inherited — re-arm before returning into glibc */
                long r = shim_raw_syscall6(nr, a1, a2, a3, a4, a5, a6);
                if (r == 0 && g_sud_on) sud_arm();
                return r;
            }
            if ((fl & CLONE_VM) && (fl & CLONE_THREAD)) {
                /* kernel contract first: CLONE_THREAD requires
                 * CLONE_SIGHAND (which itself requires CLONE_VM) — a
                 * real kernel answers EINVAL, so must the emulation */
                if (!(fl & CLONE_SIGHAND)) return -EINVAL;
                /* the Go runtime's newosproc shape: adopt the raw thread
                 * into turn-taking via a pthread-backed context-restore
                 * (see shim_adopt_raw_thread).  CLONE_SETTLS callers
                 * manage libc TLS themselves — unsupported, refuse */
                if ((fl & CLONE_SETTLS) || !t_cur_uc) return -ENOSYS;
                return shim_adopt_raw_thread((ucontext_t *)t_cur_uc, fl,
                                             a2, a3, a4);
            }
            if (fl & CLONE_VM)
                /* CLONE_VM without CLONE_THREAD (vfork-like sharing):
                 * the child of a re-executed clone would resume on the
                 * new stack inside our handler frame: refuse (use
                 * pthreads or plain fork, both fully virtualized) */
                return -ENOSYS;
            WRAPRET(fork()); /* fork-like raw clone */
        }
        case SYS_clone3: {
            /* struct clone_args: u64 flags first.  Fork-like clone3 routes
             * through the fork wrapper; CLONE_VM is refused like SYS_clone
             * (glibc falls back to clone/fork on ENOSYS) */
            if (!a1 || (size_t)a2 < 8) return -EINVAL;
            unsigned long fl3;
            memcpy(&fl3, (void *)a1, 8);
            if (t_in_fork) {
                long r = shim_raw_syscall6(nr, a1, a2, a3, a4, a5, a6);
                if (r == 0 && g_sud_on) sud_arm();
                return r;
            }
            if (fl3 & CLONE_VM) return -ENOSYS;
            WRAPRET(fork());
        }
        case SYS_waitid: {
            /* map onto the simulated wait path (a native waitid would
             * block outside the turn and wedge the simulation) */
            int idtype = (int)a1;
            siginfo_t *infop = (siginfo_t *)a3;
            int wopts = (int)a4;
            if (idtype != P_ALL && idtype != P_PID)
                return -EINVAL; /* P_PGID/P_PIDFD: not tracked */
            pid_t wpid = idtype == P_ALL ? -1 : (pid_t)a2;
            int status = 0;
            errno = 0;
            pid_t r = waitpid(wpid, &status,
                              (wopts & WNOHANG) ? WNOHANG : 0);
            if (r < 0) return errno ? -(long)errno : -EINVAL;
            if (infop) {
                memset(infop, 0, sizeof(*infop));
                if (r > 0) {
                    infop->si_signo = SIGCHLD;
                    infop->si_pid = r;
                    if (WIFEXITED(status)) {
                        infop->si_code = CLD_EXITED;
                        infop->si_status = WEXITSTATUS(status);
                    } else {
                        infop->si_code = CLD_KILLED;
                        infop->si_status = WTERMSIG(status);
                    }
                }
            }
            return 0;
        }
        case SYS_execve:
            WRAPRET(shim_execve((const char *)a1, (char *const *)a2,
                                (char *const *)a3));
        case SYS_wait4:
            WRAPRET(wait4((pid_t)a1, (int *)a2, (int)a3,
                          (struct rusage *)a4));
        case SYS_exit:
            if (t_boot) {
                /* ADOPTED thread retiring (Go-style runtimes don't use
                 * pthread_exit): longjmp back into the trampoline frame
                 * on the PTHREAD stack first — ctid clear, farewell,
                 * and teardown all happen there, after the app's clone
                 * stack can never be touched again (a joiner may reuse
                 * or unmap it the moment it observes the clear).  The
                 * table slot frees here while the turn is still held
                 * (create/retire churn would exhaust SHIM_MAX_THREADS
                 * otherwise); the abandoned signal frame is just stack
                 * memory, and the handler-era sigmask stays — a dying
                 * thread never notices. */
                adopt_boot *boot = t_boot;
                t_boot = NULL;
                boot->exit_val = (void *)(uintptr_t)a1;
                thread_table_remove(pthread_self());
                siglongjmp(boot->retire, 1);
            }
            /* a pthread-created worker or the MAIN thread retiring by
             * raw SYS_exit: farewell (vtid 0 = main retiring while
             * workers run — the manager stops servicing its channel,
             * like the pthread_exit wrapper), then the OS thread dies */
            if (g_ready) thread_send_exit((void *)(uintptr_t)a1);
            return shim_raw_syscall6(SYS_exit, a1, 0, 0, 0, 0, 0);
        case SYS_exit_group:
            g_exit_code = (int)a1;
            send_farewell();
            return shim_raw_syscall6(SYS_exit_group, a1, 0, 0, 0, 0, 0);
        case SYS_uname:
            WRAPRET(uname((struct utsname *)a1));
        case SYS_kill:
            WRAPRET(kill((pid_t)a1, (int)a2));
        case SYS_alarm:
            return (long)alarm((unsigned int)a1);
        case SYS_setitimer:
            WRAPRET(setitimer((int)a1, (const struct itimerval *)a2,
                              (struct itimerval *)a3));

        /* ---- signal-interface protection (kernel structs, not glibc's;
         * the libc-level sigaction/signal wrappers cover PLT calls) ---- */
        case SYS_rt_sigaction:
            if ((int)a1 == SIGSYS && (g_sud_on || g_seccomp_on) && a2) {
                if (a3) memset((void *)a3, 0, sizeof(struct shim_ksigaction));
                return 0; /* accepted and ignored: the backstop stays */
            }
            if (a2 && (int)a1 >= 1 && (int)a1 <= 64) {
                const struct shim_ksigaction *ka =
                    (const struct shim_ksigaction *)a2;
                if ((int)a1 == SIGSEGV && g_tsc_on) {
                    /* raw-installed SEGV handlers (Go runtime startup)
                     * must chain behind the TSC trap, not displace it:
                     * a displaced trap turns the next rdtsc into a
                     * spurious SEGV in the app's handler */
                    struct sigaction sa_c;
                    memset(&sa_c, 0, sizeof(sa_c));
                    sa_c.sa_handler = (sighandler_t)ka->handler;
                    sa_c.sa_flags = (int)ka->flags &
                                    ~(SHIM_SA_RESTORER);
                    memcpy(&sa_c.sa_mask, &ka->mask, 8);
                    struct sigaction old;
                    tsc_chain_sigaction(&sa_c, &old);
                    publish_disposition((int)a1,
                                        (sighandler_t)ka->handler);
                    if (a3) {
                        struct shim_ksigaction kold;
                        memset(&kold, 0, sizeof(kold));
                        kold.handler = (void *)old.sa_handler;
                        kold.flags = (unsigned long)old.sa_flags;
                        memcpy(&kold.mask, &old.sa_mask, 8);
                        memcpy((void *)a3, &kold, sizeof(kold));
                    }
                    return 0;
                }
                /* execute natively NOW so the mirror only records
                 * kernel-accepted dispositions (a rejected sigaction must
                 * not flip the manager-visible bitmap) */
                long r = shim_raw_syscall6(SYS_rt_sigaction, a1, a2, a3, a4,
                                           a5, a6);
                if (r == 0)
                    publish_disposition((int)a1, (sighandler_t)ka->handler);
                return r;
            }
            *handled = 0;
            return 0;
        case SYS_rt_sigprocmask:
            /* a blocked SIGSYS turns the next dispatch into a forced
             * kill: strip it from any blocking set */
            if (g_sud_on && a2 && (size_t)a4 >= 8 &&
                ((int)a1 == SIG_BLOCK || (int)a1 == SIG_SETMASK)) {
                uint64_t m;
                memcpy(&m, (void *)a2, 8);
                m &= ~(1ull << (SIGSYS - 1));
                return shim_raw_syscall6(SYS_rt_sigprocmask, a1, (long)&m, a3,
                                         8, 0, 0);
            }
            *handled = 0;
            return 0;

        /* ---- file metadata / host-state hermeticity (the scrub layer
         * above; scrub_* are no-ops before the channel is up) ---- */
        case SYS_stat:
        case SYS_lstat:
        case SYS_fstat: {
            long r = shim_raw_syscall6(nr, a1, a2, 0, 0, 0, 0);
            if (r == 0) scrub_stat((struct stat *)a2);
            return r;
        }
        case SYS_newfstatat: {
            long r = shim_raw_syscall6(nr, a1, a2, a3, a4, 0, 0);
            if (r == 0) scrub_stat((struct stat *)a3);
            return r;
        }
        case SYS_statx: {
            long r = shim_raw_syscall6(nr, a1, a2, a3, a4, a5, 0);
            if (r == 0) scrub_statx((struct statx *)a5);
            return r;
        }
        case SYS_getdents64: {
            /* clamp the batch so it always fits the sort scratch — the
             * caller just sees a smaller batch and loops */
            long cap = a3 > DENTS_BYTES && g_shm ? DENTS_BYTES : a3;
            long r = shim_raw_syscall6(nr, a1, a2, cap, 0, 0, 0);
            if (r > 0 && g_shm) return scrub_getdents((char *)a2, r);
            return r;
        }
#ifdef SYS_close_range
        case SYS_close_range: {
            long r = shim_raw_syscall6(nr, a1, a2, a3, 0, 0, 0);
            if (r == 0) {
                long hi = a2 < SHIM_MAX_FDS - 1 ? a2 : SHIM_MAX_FDS - 1;
                for (long f = a1 < 0 ? 0 : a1; f <= hi; f++) {
                    fd_meta_reset((int)f);
                    fd_fifo_cache[f] = 0;
                    if (g_ready) epoll_forget_fd((int)f);
                }
            }
            return r;
        }
#endif
        case SYS_unlink:
            meta_forget_path(AT_FDCWD, (const char *)a1, 0);
            break;
        case SYS_unlinkat:
            meta_forget_path((int)a1, (const char *)a2, 0);
            break;
        case SYS_rename:
            meta_forget_path(AT_FDCWD, (const char *)a2, 0);
            break;
        case SYS_renameat:
        case SYS_renameat2:
            meta_forget_path((int)a3, (const char *)a4, 0);
            break;
        case SYS_utimensat: {
            long r = shim_raw_syscall6(nr, a1, a2, a3, a4, 0, 0);
            if (r == 0)
                meta_note_utimens((int)a1, (const char *)a2,
                                  (const struct timespec *)a3, (int)a4);
            return r;
        }
        case SYS_utimes:
        case SYS_utime: {
            long r = shim_raw_syscall6(nr, a1, a2, 0, 0, 0, 0);
            if (r == 0 && g_shm) {
                /* legacy forms: map to "set to sim-now" (their
                 * second-granularity payloads come from the app's
                 * simulated clock anyway) */
                struct stat st;
                if (shim_raw_syscall6(SYS_newfstatat, AT_FDCWD, a1,
                                      (long)&st, 0, 0, 0) == 0)
                    meta_note((uint64_t)st.st_dev, (uint64_t)st.st_ino,
                              sim_now_ns());
            }
            return r;
        }
        case SYS_sysinfo:
            if (!g_shm) break;
            return emu_sysinfo((struct sysinfo *)a1);
        case SYS_sched_getaffinity: {
            if (!g_shm) break;
            size_t len = (size_t)a2;
            unsigned long *mask = (unsigned long *)a3;
            if (len < sizeof(unsigned long)) return -EINVAL;
            if (!mask) return -EFAULT;
            memset(mask, 0, len);
            mask[0] = 1; /* the modeled single CPU (vdso_repl_getcpu) */
            return (long)sizeof(unsigned long);
        }
        case SYS_socketpair:
            WRAPRET(socketpair((int)a1, (int)a2, (int)a3, (int *)a4));
        case SYS_open: {
            long fd = maybe_open_synth_proc((const char *)a1, a2);
            if (fd >= 0) return fd;
            break;
        }
        case SYS_openat: {
            long fd = maybe_open_synth_proc((const char *)a2, a3);
            if (fd >= 0) return fd;
            break;
        }
        case SYS_pwrite64:
        case SYS_pwritev:
        case SYS_pwritev2: {
            long r = shim_raw_syscall6(nr, a1, a2, a3, a4, a5, a6);
            if (r > 0) meta_note_write((int)a1);
            return r;
        }
        case SYS_statfs:
        case SYS_fstatfs: {
            /* filesystem stats are host state (free space changes run to
             * run): answer fixed modeled figures after the real call
             * proves the path/fd valid */
            long r = shim_raw_syscall6(nr, a1, a2, 0, 0, 0, 0);
            if (r == 0 && g_shm) {
                struct statfs *sf = (struct statfs *)a2;
                sf->f_type = 0x01021994; /* TMPFS_MAGIC */
                sf->f_bsize = sf->f_frsize = 4096;
                sf->f_blocks = (16ull << 30) / 4096;
                sf->f_bfree = sf->f_bavail = (8ull << 30) / 4096;
                sf->f_files = 1 << 20;
                sf->f_ffree = 1 << 19;
                memset(&sf->f_fsid, 0, sizeof(sf->f_fsid));
            }
            return r;
        }
        case SYS_getrusage: {
            if (!g_shm) break;
            struct rusage *ru = (struct rusage *)a2;
            int who = (int)a1;
            if (who != RUSAGE_SELF && who != RUSAGE_CHILDREN &&
                who != RUSAGE_THREAD)
                return -EINVAL;
            if (!ru) return -EFAULT;
            memset(ru, 0, sizeof(*ru));
            /* SELF/THREAD: CPU time on the modeled clock (the CPU
             * model's syscall latencies are folded into sim time);
             * CHILDREN: zeros (child accounting is not modeled).
             * Fixed modeled maxrss either way. */
            if (who != RUSAGE_CHILDREN) {
                uint64_t up = sim_now_ns() - SHIM_SIM_EPOCH_NS;
                ru->ru_utime.tv_sec = (time_t)(up / 1000000000ull);
                ru->ru_utime.tv_usec =
                    (suseconds_t)((up % 1000000000ull) / 1000);
            }
            ru->ru_maxrss = 16384; /* KiB */
            return 0;
        }
        case SYS_times: {
            if (!g_shm) break;
            struct tms *tb = (struct tms *)a1;
            uint64_t up = sim_now_ns() - SHIM_SIM_EPOCH_NS;
            long ticks = (long)(up / (1000000000ull / 100)); /* HZ=100 */
            if (tb) {
                tb->tms_utime = ticks;
                tb->tms_stime = 0;
                tb->tms_cutime = 0;
                tb->tms_cstime = 0;
            }
            return ticks;
        }
        case SYS_sched_setaffinity: {
            /* the modeled host has one CPU (cpu 0): masks that include
             * it are accepted and ignored; masks that exclude it answer
             * EINVAL exactly like a real 1-CPU kernel */
            if (!g_shm) break;
            size_t len = (size_t)a2;
            const unsigned long *mask = (const unsigned long *)a3;
            if (!mask || len < sizeof(unsigned long)) return -EINVAL;
            if (!(mask[0] & 1ul)) return -EINVAL;
            return 0;
        }
        default:
            *handled = 0;
            return 0;
    }
    *handled = 0;
    return 0;
}
